"""Arithmetic from a run's step-finish times to its end-to-end numbers."""
import math


def intervals_ms(finish_times):
    """Milliseconds between consecutive finishes."""
    return [1e3 * (b - a) for a, b in zip(finish_times, finish_times[1:])]


def throughput(finish_times, units_per_step):
    """Units per second over the whole steps between the first and the last
    finish: ``n - 1`` steps in ``t[-1] - t[0]`` seconds.  ``None`` where
    fewer than two steps finished."""
    if len(finish_times) < 2:
        return None
    span = finish_times[-1] - finish_times[0]
    return (len(finish_times) - 1) * units_per_step / span


def percentile(values, q):
    """Nearest-rank percentile: the smallest value with at least ``q`` % of
    the samples at or below it."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def loss_checks(losses, reference, rtol):
    """The three loss conditions of ``correct``: ``(ok, reasons)``.

    (a) every loss finite; (b) the first ``len(reference)`` losses within
    ``rtol`` of the reference's; (c) the mean of the last five below the
    mean of the first five (of each half, where fewer than ten)."""
    reasons = []
    if not losses or not all(math.isfinite(v) for v in losses):
        reasons.append("non-finite loss")
    if len(losses) < len(reference) or not reference:
        reasons.append("fewer losses than the reference has")
    for i, (got, want) in enumerate(zip(losses, reference)):
        if not abs(got - want) <= rtol * abs(want):
            reasons.append(f"loss {i} is {got}, the reference has {want} "
                           f"(rtol {rtol})")
    k = min(5, len(losses) // 2)
    if k < 1:
        reasons.append("too few losses to see a fall")
    elif not sum(losses[-k:]) / k < sum(losses[:k]) / k:
        reasons.append("loss did not fall")
    return not reasons, reasons
