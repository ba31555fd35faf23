"""Device: share of the traced slice with no op running on the chip, %."""
from perfbench.layer_metrics._common import idle_pct as read  # noqa: F401
