"""The trace reduction: interval arithmetic by hand, and a trace recorded
on one TPU v5e chip."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_subtract():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert u == [(0, 3), (5, 8)]
    assert tr.length(u) == 6
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def test_exposed_collective_rule():
    """A collective counts as exposed only where no other op runs."""
    coll = tr.union([(0, 10), (20, 30)])
    other = tr.union([(5, 25)])
    assert tr.length(tr.subtract(coll, other)) == 5 + 5


def test_hlo_names_and_opcodes():
    name, op = tr.parse_hlo(
        '%jvp_zero.qwz_gather_.10 = (s8[1,1024]{1,0:T(4,128)(4,1)S(1)}, '
        'f32[1,4]{1,0:T(1,128)S(1)}) custom-call(f32[1,1024]{1,0} %b), '
        'custom_call_target="tpu_custom_call"')
    assert (name, op) == ("%jvp_zero.qwz_gather_.10 = (s8[1,1024], f32[1,4])",
                          "custom-call")
    assert tr.parse_hlo("%w.2 = (s32[]{:T(128)}) while((s32[]) %t)")[1] \
        == "while"
    assert tr.parse_hlo("%a.1 = bf16[8]{0} all-gather-start(bf16[2] %x)")[1] \
        == "all-gather-start"


def test_gaps_take_the_host_span_that_overlaps_most():
    spans = [tr.Op("bench.dispatch", 0, 4), tr.Op("bench.loss_read", 4, 20)]
    assert tr._labels([(1, 3), (3, 9), (30, 40)], spans) == \
        ["bench.dispatch", "bench.loss_read", "other"]


def test_window_takes_in_device_ops_that_lead_the_host_span():
    """Device timestamps can lead the host's: an operation that starts
    before the ``bench.window`` span still belongs to the window."""
    ops = {0: [tr.Op("a", 95, 120), tr.Op("b", 130, 190)],
           1: [tr.Op("c", 101, 140)]}
    win = [tr.Op("bench.dispatch", 100, 102), tr.Op("bench.window", 100, 200)]
    assert tr.window(win, ops) == (95, 200)
    assert tr.window(win[:1], ops) == (95, 190)
    late = [tr.Op("bench.window", 100, 180)]
    assert tr.window(late, ops) == (95, 190)


TRACE_1X1 = os.path.join(DATA, "trace_1x1.xplane.pb")
GPT350M_SHAPES = {"embed": (51511296,), "blocks": (24, 16779264),
                  "head": (1024,), "unemb": (4, 12877824)}


@pytest.fixture(scope="module")
def one_chip():
    """Two gpt-350m zeropp steps on one TPU v5e chip (batch 8 x 1024),
    each batch made between the steps under ``bench.*`` host spans."""
    from bench import flops
    return tr.summarize(TRACE_1X1, flops.QUANT_KERNELS)


def test_one_chip_busy_union(one_chip):
    d = one_chip.devices[0]
    assert one_chip.window_ns == 1309792059
    assert d.busy_ns == 1298668561
    assert one_chip.busy_s == pytest.approx(1.298668561)
    assert d.busy_ns + sum(b - a for a, b in d.gaps) == one_chip.window_ns


def test_one_chip_kernel_events_are_the_counted_calls(one_chip):
    from bench import flops
    calls = flops.quant_kernel_calls(GPT350M_SHAPES, world=1, intra=1,
                                     prefetch=1)
    assert calls["calls_per_step"] == 155
    assert one_chip.devices[0].kernel_count == 2 * calls["calls_per_step"]
    assert one_chip.devices[0].kernel_ns == 68542397


def test_one_chip_has_no_collectives_and_labels_its_gaps(one_chip):
    assert one_chip.devices[0].collective_exposed_ns == 0
    gaps = one_chip.breakdown()["idle_gaps"]
    assert gaps[0][0] == "bench.make_batch"
    assert gaps[0][1] == pytest.approx(0.010963213)
    ops = one_chip.breakdown()["device_ops"]
    assert len(ops) == 10 and not any(" while(" in k for k, _ in ops)
