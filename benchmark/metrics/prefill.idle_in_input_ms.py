"""Device-idle time while a prompt tensor is built, uploaded and launched
(``prefill.input``, ``prefill.forward``) per prime in the trace."""
from benchmark.metrics._spans import idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], ("prefill.input", "prefill.forward"),
                       "prefill.forward")
