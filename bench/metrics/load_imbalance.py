"""Load imbalance of a solve whose lanes are sharded over chips: the most
accepted steps any shard took over the mean of the shards
(``parallel/solve.py: with_shard_load_stats``), a count the program keeps.
Read on the batches of the last steps run, at the current parameters; 1.0
is a perfect balance."""


def read(ctx):
    fn = getattr(ctx.job, "load_imbalance", None)
    return None if fn is None else fn()
