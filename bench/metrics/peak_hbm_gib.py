"""Device memory the training step needs, on the fullest chip, read from
the device runtime after the window: the high-water mark of buffers
(``peak_bytes_in_use``) plus that of the region the TPU runtime reserves
for the program's temporaries (``peak_bytes_reserved``).  The first alone
leaves the temporaries out (PERF.md)."""


def read(ctx):
    return ctx.memory_peak_bytes / 2 ** 30
