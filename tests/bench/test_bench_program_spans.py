"""The program's own spans and stamps in a traced CPU run of the harness:
the readers of the stamps give numbers, the ``serve.*`` spans agree with
the harness's records of the same calls, and the trace reduction reads
only the harness's ``bench.*`` spans, as before the program had any."""
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from bench import harness
from bench.spec import Spec
from bench.trace import Trace, find_xplane
from benchutil import make_root

SEED = 2 ** 32 + 7
STAMP_METRICS = ("admit_wait_p90_ms.code", "prefill_to_token_p90_ms.code")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A traced run of the tiny code cell whose trace is kept."""
    root = make_root(tmp_path_factory.mktemp("root"))
    keep = tmp_path_factory.mktemp("trace")
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "tempfile",
               SimpleNamespace(mkdtemp=lambda prefix: str(keep)))
    mp.setattr(harness, "shutil",
               SimpleNamespace(rmtree=lambda *a, **k: None))
    try:
        run = harness.Run(root, "tiny.code", SEED, 1.5, True,
                          require_chip=False, log=lambda s: None)
        res = run.execute()
    finally:
        mp.undo()
    return root, run, res, find_xplane(keep)


def _serve_events(path: Path, name: str):
    """``(start_ns, duration_ns, stats)`` of the host events ``name``."""
    data = ProfileData.from_file(str(path))
    return sorted(((ev.start_ns, ev.duration_ns, dict(ev.stats))
                   for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name == name), key=lambda e: e[0])


def _contains(seq, part):
    """``part`` is a contiguous run of ``seq``."""
    return any(seq[i:i + len(part)] == part
               for i in range(len(seq) - len(part) + 1))


def test_stamp_readers_read_the_code_cell(traced):
    _, _, res, _ = traced
    assert res["correct"], res["checks"]
    for name in STAMP_METRICS:
        assert res["metrics"][name]["unit"] == "ms"
        assert res["metrics"][name]["value"] >= 0
    assert res["metrics"]["prefill_to_token_p90_ms.code"]["value"] > 0


def test_program_spans_agree_with_the_harness_records(traced):
    _, run, _, xplane = traced
    decode = [(s["rows"], s["live_tokens"])
              for *_, s in _serve_events(xplane, "serve.decode")]
    prefill = [(s["start"], s["tokens"])
               for *_, s in _serve_events(xplane, "serve.prefill")]
    assert decode and prefill
    # the trace holds the window's calls, the records every call of the run
    assert _contains([(len(rows), sum(rows)) for _, rows in run.decode_calls],
                     decode)
    assert _contains([(start, n) for _, start, n in run.prefill_calls],
                     prefill)


def test_reduction_reads_only_the_harness_spans(traced):
    _, _, _, xplane = traced
    tr = Trace(xplane)
    assert set(tr.spans) == {"bench.window", "bench.step", "bench.plan",
                             "bench.prefill", "bench.decode"}
    commits = _serve_events(xplane, "serve.commit")
    assert commits
    assert {tr.innermost_span(a + d / 2) for a, d, _ in commits} == \
        {"bench.step"}


def test_stamp_readers_give_nothing_without_the_stamps(traced):
    """A program whose requests carry no ``admitted_at`` (before the stamp
    existed): both readers give nothing, and do not raise."""
    root, *_ = traced
    spec = Spec(root)
    old = SimpleNamespace(arrival=1.0, first_token_at=2.0)
    ctx = SimpleNamespace(requests=[
        SimpleNamespace(kind="window", req=old),
        SimpleNamespace(kind="window", req=None)])
    for name in STAMP_METRICS:
        assert spec.reader(name).read(ctx) is None
