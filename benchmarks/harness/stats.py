"""The arithmetic from completion stamps to end-to-end metrics."""

import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default), on a copy."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def intervals(begin, stamps):
    """Completion intervals: from the window's begin to the first stamp,
    then stamp to stamp.  One per completed step."""
    out, prev = [], begin
    for t in stamps:
        out.append(t - prev)
        prev = t
    return out


def samples_per_s_per_chip(begin, stamps, samples_per_step, chips):
    """All the samples whose step completed in the window over all the
    window's time (begin to the last stamp), per chip."""
    if not stamps:
        raise ValueError("no step completed in the window")
    return len(stamps) * samples_per_step / (stamps[-1] - begin) / chips


def cycle_means(losses, pool):
    """Mean loss over each whole cycle of the pool, in order."""
    return [sum(losses[i:i + pool]) / pool
            for i in range(0, len(losses) - pool + 1, pool)]
