"""The checks every driver makes of the device path, from the counters of
the process that holds the chip (``readings.counters['program_counter']``,
differenced over the window)."""

from __future__ import annotations

from benchmarks.lib import readers
from benchmarks.lib.report import Checks

# a fallback for one of these reasons means the device path broke and a
# safety rung served the lanes; small-batch / unsupported are policy
FORBIDDEN_FALLBACKS = "reason=(device-error|deadline|breaker-open|probe-failed)"


def _count(r: readers.Readings, name: str, field: str, labels: str = None):
    term = {"source": "program_counter", "name": name, "field": field}
    if labels:
        term["labels"] = labels
    return readers.term_value(term, "", r) or 0


def device_path(checks: Checks, r: readers.Readings, on_chip: bool,
                least_on_kernel: int) -> float:
    """No lane on a forbidden fallback; on a chip, every dispatch on
    ``tpu/pallas`` and at least ``least_on_kernel`` of them. -> dispatches
    in the window."""
    checks.at_most("forbidden_fallback_lanes", _count(
        r, "crypto_cpu_fallback_total", "value", FORBIDDEN_FALLBACKS), 0)
    total = _count(r, "crypto_verify_latency_seconds", "count")
    if on_chip:
        on_kernel = _count(r, "crypto_verify_latency_seconds", "count",
                           "backend=tpu,impl=pallas$")
        checks.at_most("dispatches_off_kernel", total - on_kernel, 0)
        checks.at_least("dispatches_on_kernel", on_kernel, least_on_kernel)
    return total


def lanes_on_device(checks: Checks, tag: str, r: readers.Readings,
                    on_chip: bool, least_lanes: int) -> None:
    """Over calls made for the comparison alone: at least ``least_lanes``
    lanes dispatched (on a chip: to ``tpu/pallas``, every dispatch), none
    on the serial small-batch path the sigcache can divert a call to, none
    on a forbidden fallback."""
    checks.at_least(f"{tag}_lanes_dispatched", _count(
        r, "crypto_batch_size$", "sum", "backend=tpu$" if on_chip else None),
        least_lanes)
    checks.at_most(f"{tag}_lanes_off_device", _count(
        r, "crypto_cpu_fallback_total", "value"), 0)
    if on_chip:
        checks.at_most(f"{tag}_dispatches_off_kernel", _count(
            r, "crypto_verify_latency_seconds", "count") - _count(
            r, "crypto_verify_latency_seconds", "count",
            "backend=tpu,impl=pallas$"), 0)
