"""The trace reduction on a hand-made event list (overlaps, gaps, nesting, two
device lines) and on a sample recorded from one of PR 24's traced chip runs."""

from pathlib import Path

import pytest

from benchmark import readers
from benchmark.readers import (device_idle_share, kernel_time_share,
                               span_share, step_mfu, trace)

SAMPLE = Path(__file__).parent / "data" / "trace_sample.json.gz"

# device 0: steps start at 1.0, 3.0, 5.0; operations overlap, nest and leave gaps
OPS0 = [("fusion.1", 1.0, 0.5), ("flash_fwd", 1.4, 0.4),      # overlap: 1.0-1.8
        ("while.2", 2.0, 0.6), ("flash_bwd", 2.1, 0.3),       # nested in while
        ("fusion.1", 3.0, 1.0), ("copy.3", 4.5, 0.25),
        ("fusion.1", 5.0, 1.0)]                               # past the cut
MOD0 = [("jit__step_fn(1)", 1.0, 1.7), ("jit__step_fn(1)", 3.0, 1.8),
        ("jit__step_fn(1)", 5.0, 1.0), ("jit_other", 0.2, 0.1)]
OPS1 = [("fusion.1", 1.5, 1.0), ("fusion.1", 3.5, 1.0)]
MOD1 = [("jit__step_fn(1)", 1.5, 1.0), ("jit__step_fn(1)", 3.5, 1.0),
        ("jit__step_fn(1)", 5.5, 1.0)]


def ctx(devices, **kw):
    base = dict(config={"family": "decoder", "model": {
        "hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 16,
        "num_hidden_layers": 1, "vocab_size": 32}},
        mix={"shape": {"batch": 1, "seq_len": 16}}, chips=len(devices or [0]),
        spans=[], host_interval=None, devices=devices,
        step_module="_step_fn", peak={"bf16_flops_per_s": 1e6})
    base.update(kw)
    return readers.Context(**base)


def test_interval_is_cut_to_whole_steps():
    d = trace.DeviceTrace("/device:TPU:0", OPS0, MOD0)
    assert trace.step_interval(d, "_step_fn") == (1.0, 5.0, 2)
    assert trace.step_interval(d, "_step_fn", skip=1) == (3.0, 5.0, 1)
    assert trace.step_interval(d, "_step_fn", skip=2) is None
    assert trace.step_interval(trace.DeviceTrace("x", [], MOD0[:1]), "_step_fn") is None


def test_busy_union_gaps_and_self_time():
    ops = trace.clip(OPS0, 1.0, 5.0)
    assert [(round(a, 9), round(b, 9)) for a, b in trace.busy_intervals(ops)] == [
        (1.0, 1.8), (2.0, 2.6), (3.0, 4.0), (4.5, 4.75)]
    assert trace.busy_seconds(ops) == pytest.approx(2.65)
    gaps = trace.idle_gaps(OPS0, 1.0, 5.0)
    assert [(round(a, 2), round(b, 2)) for a, b in gaps] == [
        (1.8, 2.0), (2.6, 3.0), (4.0, 4.5), (4.75, 5.0)]
    own = trace.self_seconds(ops)
    assert own["while.2"] == pytest.approx(0.3)       # its body's 0.3 taken out
    assert own["flash_bwd"] == pytest.approx(0.3)
    assert own["fusion.1"] == pytest.approx(1.4)       # 0.1 under flash_fwd
    assert sum(own.values()) == pytest.approx(2.65)


def test_readers_over_two_device_lines():
    devs = [trace.DeviceTrace("/device:TPU:0", OPS0, MOD0),
            trace.DeviceTrace("/device:TPU:1", OPS1, MOD1)]
    c = ctx(devs)
    # device 0: 1 - 2.65/4; device 1: cut 1.5..5.5, busy 2.0 of 4.0
    assert device_idle_share.read(c) == pytest.approx(
        100 * ((1 - 2.65 / 4) + 0.5) / 2)
    # flash events: 0.4 + 0.3 on device 0, none on device 1; busy 2.65 + 2.0
    assert kernel_time_share.read(c, pattern="flash") == pytest.approx(
        100 * 0.7 / 4.65)
    assert kernel_time_share.read(c, pattern="nothing_like_it") is None
    from benchmark import flops

    per_step = flops.train_step_flops(c.config, c.mix["shape"])
    assert step_mfu.read(c) == pytest.approx(100 * 2 * per_step / 4.0 / (2 * 1e6))


def test_a_reader_that_finds_nothing_returns_nothing():
    c = ctx(None)
    assert device_idle_share.read(c) is None and step_mfu.read(c) is None
    assert kernel_time_share.read(c, pattern="flash") is None
    assert span_share.read(c, span="data_wait") is None
    one_step = ctx([trace.DeviceTrace("/device:TPU:0", OPS0, MOD0[:1])])
    assert device_idle_share.read(one_step) is None


def test_span_share_clips_to_the_traced_interval():
    spans = [{"name": "data_wait", "start": 9.0, "dur_s": 2.0},   # half inside
             {"name": "data_wait", "start": 12.0, "dur_s": 1.0},
             {"name": "step", "start": 10.0, "dur_s": 5.0},
             {"name": "data_wait", "start": 30.0, "dur_s": 1.0}]  # outside
    c = ctx(None, spans=spans, host_interval=(10.0, 20.0))
    assert span_share.read(c, span="data_wait") == pytest.approx(20.0)
    assert span_share.read(c, span="ckpt") is None


def test_recorded_sample_from_the_chip():
    """A few steps of mistral7b-s8192 on the v5e chip (PR 24), reduced to the
    operations and modules lines."""
    devs = trace.load_saved(SAMPLE)
    assert len(devs) == 1 and devs[0].name == "/device:TPU:0"
    t0, t1, steps = trace.step_interval(devs[0], "_step_fn")
    assert steps >= 2 and t1 > t0
    ops = trace.clip(devs[0].ops, t0, t1)
    busy = trace.busy_seconds(ops)
    assert 0.9 * (t1 - t0) < busy <= (t1 - t0) * (1 + 1e-9)
    assert sum(trace.self_seconds(ops).values()) == pytest.approx(busy, rel=1e-6)
    c = ctx(devs)
    share = kernel_time_share.read(c, pattern=FLASH_PATTERN)
    assert share is not None and 5 < share < 80


FLASH_PATTERN = r"custom-call\(bf16\[\d+,\d+,\d+,\d+\]"
