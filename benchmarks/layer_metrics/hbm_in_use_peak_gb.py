"""device layer: ``peak_bytes_in_use`` of the fullest chip, GB: the runtime's
book of buffers (state, staged feeds, fetches, what set-up left), from the
program's ``device_memory_bytes`` after ``telemetry.sample_device_memory()``.
The fullest chip is the one whose ``peak_bytes_in_use`` +
``peak_bytes_reserved`` is largest, the harness's own choice for
``hbm_peak_gb``; ``hbm_reserved_peak_gb`` reads the same chip."""

from harness import memory_gauges


def read(ctx):
    peaks = memory_gauges.device_peaks()
    return None if peaks is None else peaks[0]
