"""Process start to the window's first request: data made and written,
server started, jax initialised, every shape warmed (s)."""


def read(ctx: dict):
    return ctx["setup_s"]
