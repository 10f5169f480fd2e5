"""Seconds from process start to the first timed request: imports,
weights made on the device, engine construction, compilation (or the
persistent cache's load) and warm-up on every shape the traffic uses."""


def read(ctx):
    return ctx.setup_s
