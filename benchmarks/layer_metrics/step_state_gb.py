"""dispatch layer: the persistables the step is called with, on one device,
GB: the program's ``step_resident_bytes``, ``parameter`` + ``optimizer_state``
+ ``other_state`` of the step's signature (logged apart).  What the scope
holds between steps; ``hbm_in_use_peak_gb`` holds it at least once, and twice
where something copies the state without donating it."""

from harness import memory_gauges


def read(ctx):
    return memory_gauges.step_state_gb()
