"""Engine host loop: window seconds over engine steps, in ms."""
from perfbench.layer_metrics._common import host_step_ms as read  # noqa: F401
