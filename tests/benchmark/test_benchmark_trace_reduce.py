"""``trace_reduce.py`` on a recorded device trace (the first 520 ms of the
first traced window of ``gpt2-xl.train-1chip`` on a v5e chip, PR 23,
trimmed to the device's op and module lines and the ``bench.*`` host
spans) gives the same busy share, kernel sums and gap attribution every
time; and its interval arithmetic on hand-made events."""

import os

import pytest

import benchtiny
from benchmark import trace_reduce as tr

RECORDED = os.path.join(benchtiny.ROOT, "benchmark", "testdata",
                        "train-1chip.trimmed.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(RECORDED, n_devices=1)


def test_recorded_busy_share(reduced):
    assert reduced["devices"] == ["/device:TPU:0"]
    assert reduced["window_s"] == pytest.approx(0.52, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.479158084, abs=2e-9)
    assert reduced["busy_s_by_device"] == [reduced["busy_s"]]
    share = reduced["busy_s"] / reduced["window_s"]
    assert share == pytest.approx(0.92146, abs=1e-5)


def test_recorded_self_times_add_up_to_the_busy_time(reduced):
    assert sum(reduced["op_self_s"].values()) == pytest.approx(
        reduced["busy_s"], abs=1e-8)
    # A ``while`` round the scanned layers is charged only its own time.
    whiles = [s for name, s in reduced["op_self_s"].items()
              if name.startswith("while")]
    assert whiles and max(whiles) < 0.01


@pytest.mark.parametrize("kernel,seconds", [
    ("smp_flash_fwd", 0.02378476), ("smp_flash_bwd_dq", 0.023684665),
    ("smp_flash_bwd_dkv", 0.029152734), ("smp_flash_", 0.076622159)])
def test_recorded_kernel_sums(reduced, kernel, seconds):
    assert reduced.matching(kernel) == pytest.approx(seconds, abs=2e-9)


def test_recorded_gap_attribution_and_programs(reduced):
    gaps = reduced["gaps_s"]
    assert gaps["bench.train_step"] == pytest.approx(0.030374145, abs=2e-9)
    assert gaps["none"] == pytest.approx(0.010467771, abs=2e-9)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-8)
    assert reduced["top_gaps"][0][0] == "bench.train_step"
    step = [s for name, s in reduced["module_s"].items()
            if name.startswith("jit_full_impl")]
    assert step == [pytest.approx(0.479159354, abs=2e-9)]
    assert reduced["top_ops"][0][0] == "smp_flash_bwd_dkv.8"
    assert reduced.collective_s() == 0


def test_reducing_twice_gives_the_same_numbers(reduced):
    again = tr.reduce(RECORDED, n_devices=1)
    assert again == reduced


def planes(ops, spans=(), device="/device:TPU:0"):
    return {
        device: {tr.OP_LINE: [(n, s, d, n) for n, s, d in ops],
                 tr.MODULE_LINE: []},
        "/host:CPU": {"main": [(n, s, e - s, "") for n, s, e in spans]},
    }


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_self_time_charges_a_parent_what_its_children_leave():
    events = [("while.1", 0, 100, ""), ("fusion.1", 10, 30, ""),
              ("fusion.2", 50, 40, ""), ("fusion.1", 200, 5, "")]
    totals, _ = tr.self_times(events)
    assert totals == {"while.1": 30, "fusion.1": 35, "fusion.2": 40}


def test_idle_gaps_go_to_the_innermost_span_open_at_their_middle():
    ops = [("a", 100, 100), ("b", 300, 100), ("c", 900, 50)]
    spans = [("bench.window", 0, 1000), ("bench.train_step", 180, 320),
             ("bench.wait_due", 400, 900)]
    r = tr.reduce(planes(ops, spans))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["gaps_s"] == pytest.approx({
        "none": 150e-9, "bench.train_step": 100e-9, "bench.wait_due": 500e-9})


def test_busy_time_is_averaged_over_the_devices_used():
    data = planes([("a", 0, 100)])
    data["/device:TPU:1"] = {tr.OP_LINE: [("a", 0, 50, "a")]}
    data["/device:TPU:2"] = {tr.OP_LINE: [("a", 0, 10, "a")]}
    r = tr.reduce(data, n_devices=2)
    assert r["busy_s_by_device"] == pytest.approx([100e-9, 50e-9])
    assert r["busy_s"] == pytest.approx(75e-9)


def test_collectives_are_found_by_their_op_names():
    ops = [("all-reduce.3", 0, 10), ("fusion.1", 10, 10),
           ("collective-permute-start.2", 20, 5), ("all-gather.1", 30, 5)]
    assert tr.reduce(planes(ops)).collective_s() == pytest.approx(20e-9)


def test_a_trace_with_no_device_op_is_refused():
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce({"/host:CPU": {"main": []}})
    assert tr.short_name(
        "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") == \
        "fusion.3"
