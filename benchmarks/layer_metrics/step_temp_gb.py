"""lowering layer: the compiled step's temporaries on one device, GB: the
program's ``step_memory_bytes{kind=temp}`` (XLA's ``memory_analysis()`` of the
step's executable, stamped where the executor produced it).  On a TPU this is
what the step adds to the region the runtime reserves
(``hbm_reserved_peak_gb``), the half of ``hbm_peak_gb`` that a change to the
program moves through XLA's schedule alone.  Where more than one executable
was introspected, the one with the most temporaries is the step."""

from harness import memory_gauges


def read(ctx):
    return memory_gauges.step_temp_gb()
