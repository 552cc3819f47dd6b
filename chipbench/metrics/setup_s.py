"""Set-up: process start to the first request of the window (JAX start,
server construction with its measured calibration sweep, weights, image
pool, warm-up).  The reference runs after the window and is not in it."""


def read(ctx):
    return ctx.setup_s
