"""Session layer: programs compiled or fetched from the persistent cache
during the measured window (`jax.monitoring` events); set-up warms every
program, so anything here is a stall inside the window."""


def read(ctx):
    return ctx["window_programs"]
