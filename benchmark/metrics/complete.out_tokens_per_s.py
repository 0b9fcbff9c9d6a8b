"""Output tokens delivered inside the window over the window."""


def read(ctx):
    tokens = ctx["record"]["serve"]["tokens"]
    return tokens / ctx["record"]["window_s"] if tokens else None
