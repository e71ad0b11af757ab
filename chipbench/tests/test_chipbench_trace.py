"""The trace reduction: busy union, idle share, program time by name,
idle gaps by host span and the perf_counter-to-trace clock offset, on
a small trace in ``trace_reduce.load``'s format."""
import _paths  # noqa: F401
import json
import os

import pytest

from chipbench import trace_reduce as tr

# device 0: decode 0-100 (two ops, overlapping), prefill 150-250, idle
# 100-150 and 250-300; host: one step 0-120, one idle wait 120-300
HAND = {
    "modules": [[0, "jit_decode", 0, 100], [0, "jit_prefill", 150, 100]],
    "ops": [[0, "fusion.1", 0, 60, ""], [0, "while.2", 40, 60, ""],
            [0, "fusion.9", 150, 100, ""]],
    "host": [["bench.step", 0, 120], ["bench.idle_wait", 120, 180]],
}


def test_busy_union_and_idle_share():
    assert tr.union([(0, 60), (40, 100), (150, 250)]) == [(0, 100),
                                                           (150, 250)]
    assert tr.busy_ns(HAND, 0, 300) == 200
    assert tr.busy_ns(HAND, 50, 200) == 100
    assert tr.idle_gaps(HAND, 0, 300) == [(100, 150), (250, 300)]


def test_program_time_by_name():
    runs = tr.program_runs(HAND, "jit_decode")
    assert runs == [(0, 100)] and tr.seconds(runs) == 100 / 1e9
    assert tr.program_runs(HAND, "jit_prefill", 0, 100) == []
    assert tr.program_name("jit_decode(123456)") == "jit_decode"
    assert tr.op_name("%copy.26 = bf16[2]{0} copy(bf16[2]{0} %a)") == \
        "copy.26"


def test_ops_attributed_to_the_program_that_holds_them():
    trace = json.loads(json.dumps(HAND))
    tr._attribute(trace["ops"], trace["modules"])
    assert [op[4] for op in trace["ops"]] == ["jit_decode", "jit_decode",
                                              "jit_prefill"]
    top = tr.top_ops(trace, 0, 300)
    assert top[0] == ["jit_prefill/fusion.9", 100 / 1e9]


def test_idle_gaps_go_to_the_innermost_covering_span():
    gaps = tr.idle_gaps(HAND, 0, 300)
    labelled = [(n, s, s + d) for n, s, d in HAND["host"]] + \
        [("serve.prefill", 105, 148)]
    got = dict(tr.attribute_gaps(gaps, labelled))
    assert got == {"serve.prefill": 50 / 1e9, "bench.idle_wait": 50 / 1e9}


def test_clock_offset_pairs_steps():
    pc = [10.0, 10.5, 11.0]
    host = [p * 1e9 + 777 for p in pc]
    assert tr.clock_offset_ns(host, pc) == pytest.approx(777, abs=1e-3)
    assert tr.clock_offset_ns([], pc) is None


RECORDED = os.path.join(os.path.dirname(__file__), "trace_sample.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace sample")
def test_recorded_chip_trace():
    """A slice of a real v5e trace of the paged 0.5b cell: the decode
    program is found by name and the busy union never exceeds the
    window nor the sum of op time."""
    sample = json.load(open(RECORDED))
    trace = sample["trace"]
    lo, hi = sample["window_ns"]
    busy = tr.busy_ns(trace, lo, hi)
    assert 0 < busy <= hi - lo
    assert busy <= sum(min(s + d, hi) - max(s, lo)
                       for _, _, s, d, _ in trace["ops"]
                       if s + d > lo and s < hi)
    assert busy == pytest.approx(sample["busy_ns"])
    runs = tr.program_runs(trace, "jit_decode", lo, hi)
    assert len(runs) == sample["decode_runs"]
    assert tr.seconds(runs) == pytest.approx(sample["decode_s"])
