"""1 minus the union of device operations over the traced window."""
from benchmark.readers import idle_share as read  # noqa: F401
