"""The compiler's ``peak_memory_in_bytes`` of the cell's gradient program
alone (no optimizer, no state kept between steps), compiled for the chip
in the traced run: the memory of the gradient strategy apart from AdamW."""


def read(ctx):
    fn = getattr(ctx.job, "grad_peak_bytes", None)
    return None if fn is None else fn() / 2 ** 30
