"""What the CPU tests need declared beside ``helpers``: the end-to-end
metrics of entries added after it. ``helpers.bench`` rebuilds every
cell's end-to-end metrics from ``helpers.ENTRY_METRICS``, which is keyed
by entry; the timeline entry reports the fabric's rate."""
from perfbench.tests import helpers

helpers.ENTRY_METRICS.setdefault(
    "timeline", [("grid_steps_per_s", "steps/s", "higher")])
