"""device layer: ``peak_bytes_reserved`` of the fullest chip, GB: the region
the runtime reserves for the compiled programs' temporaries, from the
program's ``device_memory_bytes`` after ``telemetry.sample_device_memory()``,
on the chip ``hbm_in_use_peak_gb`` reads.  The largest program's temporaries
set it: the step's (``step_temp_gb``) unless set-up ran a larger one."""

from harness import memory_gauges


def read(ctx):
    peaks = memory_gauges.device_peaks()
    return None if peaks is None else peaks[1]
