"""Wall time of the whole window over the training steps completed in it
(host clock).  Each step is a host batch, the jitted donated step and the
fetch of its loss, so the window ends when the last step has finished."""


def read(ctx):
    return 1000.0 * ctx.window_s / ctx.steps
