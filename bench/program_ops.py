"""Device time of one kernel's operations inside one program, from a
reduced trace (``trace.Trace``).

The trace's operation labels are the HLO instructions' text: a Pallas
kernel is found by its name there (``mla_decode_pallas``), while a
``jax.named_scope`` lives in the instructions' metadata, which the
reduction does not keep.
"""
from typing import Callable, Optional

__all__ = ["op_time_in_program_s"]


def op_time_in_program_s(tr, program: str, match: Callable[[str], bool],
                         window: Optional[tuple] = None) -> float:
    """Summed duration of the operations whose label ``match``es and that
    start inside a run of ``program`` (an ``XLA Modules`` name), within
    ``window`` if given, averaged over the chips."""
    if not tr.ops:
        return 0.0
    acc = 0.0
    for c in tr.chips:
        for a, b, name in tr.ops[c]:
            if window and (b <= window[0] or a >= window[1]):
                continue
            if match(tr.labels[name]) and tr._module_at(c, a) == program:
                acc += b - a
    return acc / len(tr.chips) * 1e-9
