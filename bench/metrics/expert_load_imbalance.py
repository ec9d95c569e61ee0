"""Load imbalance of the held experts: for each expert layer, the most
(token, expert) assignments any held expert got over the held experts'
mean, then the mean over the layers.  Counted by the program's own router
(``nn/moe.py: moe_route``) on the depth states of the last batch
(``models/lm.py: node_depth_states``), off the timed step.  1.0 is a
perfect balance."""


def read(ctx):
    fn = getattr(ctx.job, "expert_load_imbalance", None)
    return None if fn is None else fn()
