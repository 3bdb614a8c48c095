"""The reduction from a trace to numbers, pinned on a recorded sample:
``data/qlora-wire-bf16.3rounds.xplane.pb`` is the profiled window (three
rounds) of a traced run of the ``qlora-wire`` job (packed bf16, pipelined
path) on a TPU v5 lite (PR 23, my
chip run), cut by ``tools/cut_xplane.py`` to the lines the reduction
reads.  A change to ``xplane.py`` that moves these numbers changes what
every ledger line means."""

import os

import pytest

from benchmark import harness, spans, xplane

SAMPLE = os.path.join(
    harness.ROOT, "data", "qlora-wire-bf16.3rounds.xplane.pb"
)
STEP = "jit_step_fn(1527264886761392164)"


@pytest.fixture(scope="module")
def profile():
    return xplane.load(SAMPLE)


def test_whole_sample(profile):
    s = xplane.summarize(profile)
    assert s["chips"] == s["chips_used"] == ["/device:TPU:0"]
    assert s["busy_s"] == pytest.approx(0.701712089, abs=1e-9)
    assert s["window_s"] == pytest.approx(1.771955133, abs=1e-9)
    assert s["worst_idle_share"] == pytest.approx(0.60398992, abs=1e-8)
    # Self times: a while around the scanned layers is charged only
    # what its children leave, so the operations sum to the busy time.
    assert sum(s["op_seconds"].values()) == pytest.approx(s["busy_s"])
    assert s["device_ops"][0] == [
        "fusion.963 fusion bf16[4,512,4096]", pytest.approx(0.04829273)
    ]
    assert len(s["device_ops"]) == 10
    assert s["module_seconds"][STEP] == pytest.approx(0.691586636)


def test_window_and_gap_attribution(profile):
    anchor = int(xplane.anchor_ns(profile))
    assert anchor == 49233251
    window = (anchor, anchor + 1_500_000_000)
    # A 20 ms pack inside a long wait, and 0.3 s that no span covers:
    # a span gets the idle nanoseconds it covers and no more.
    host = [
        (anchor, anchor + 400_000_000, "trainer.step"),
        (anchor + 400_000_000, anchor + 420_000_000, "trainer.pack"),
        (anchor + 400_000_000, anchor + 1_200_000_000, "mailbox.wait"),
    ]
    s = xplane.summarize(profile, window=window, host_spans=host)
    assert s["window_s"] == 1.5
    assert s["busy_s"] == pytest.approx(0.570138828, abs=1e-9)
    assert s["worst_idle_share"] == pytest.approx(0.619907448, abs=1e-9)
    assert s["idle_gaps"] == [
        ["mailbox.wait", pytest.approx(0.546016314)],
        ["(no host span)", pytest.approx(0.197580928)],
        ["trainer.step", pytest.approx(0.17737244)],
        ["trainer.pack", pytest.approx(0.00889149)],
    ]
    assert sum(dict(s["idle_gaps"]).values()) == pytest.approx(
        1.5 - s["busy_s"]
    )
    assert s["module_seconds"][STEP] == pytest.approx(0.576318938)


def test_short_name():
    text = ("%fusion.833 = bf16[4,512,4096]{2,1,0:T(8,128)(2,1)S(1)} "
            "fusion(bf16[4,512,4096]{2,1,0} %x), kind=kOutput, calls=%f")
    assert xplane.short_name(text) == "fusion.833 fusion bf16[4,512,4096]"
    assert xplane.short_name("no hlo here") == "no hlo here"


def test_busy_gaps_and_union():
    ops = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c")]
    busy, window, gaps = xplane.busy_and_gaps(ops, (0, 50))
    assert (busy, window) == (30, 50)
    assert gaps == [(20, 30), (40, 50)]
    assert xplane.op_totals([(0, 100, "%w = s32[] while(x)"),
                             (10, 40, "%f = f32[2] fusion(y)")]) == {
        "w while s32[]": 70, "f fusion f32[2]": 30,
    }
    assert spans.union_length([(0, 2), (1, 3), (5, 6)]) == 4


def test_a_gap_is_split_by_what_each_span_covers():
    """The shortest span wins the nanoseconds it covers; a span is never
    given more of a gap than it covers; the rest goes to no span."""
    gaps = [(0, 100), (200, 210)]
    host = [(0, 80, "wait"), (10, 30, "pack"), (25, 40, "send"),
            (205, 400, "wait")]
    assert xplane.attribute_gaps(gaps, host) == {
        "wait": 10 + 40 + 5, "pack": 15, "send": 15,
        xplane.NO_SPAN: 20 + 5,
    }
    assert xplane.attribute_gaps(gaps, []) == {xplane.NO_SPAN: 110}


def test_cutter_keeps_what_the_reduction_reads(tmp_path, profile):
    """Cutting the sample again (to its first 0.75 s) keeps the anchor
    and yields a trace the reduction still reads."""
    import subprocess
    import sys

    out = tmp_path / "cut.xplane.pb"
    subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "tools", "cut_xplane.py"),
         SAMPLE, str(out), "0.75"], check=True,
    )
    cut = xplane.load(str(out))
    assert xplane.anchor_ns(cut) == xplane.anchor_ns(profile)
    s = xplane.summarize(cut)
    assert 0 < s["busy_s"] < 0.701712089
    assert out.stat().st_size < os.path.getsize(SAMPLE)
