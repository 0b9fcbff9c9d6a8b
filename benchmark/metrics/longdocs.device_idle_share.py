"""1 - union of device-op intervals over the traced window, in percent."""
from benchmark.metrics._common import idle_share as read  # noqa: F401
