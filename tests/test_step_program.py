"""The compiled step says what it is made of (ISSUE 35): the map from an
executable's instructions to the program's own parts (``obs.program``), the
trainer's one lower -> compile path, and the ``step_program`` span."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import flax.linen as nn  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from tpucfn.obs.program import (  # noqa: E402
    PASSES, memory_attrs, pass_of, scope_map, scope_of)

ROOT = Path(__file__).resolve().parent.parent
LINE_LIMIT = 512 * 1024


# -- the map ------------------------------------------------------------------

class Block(nn.Module):
    @nn.compact
    def __call__(self, x, _):
        h = nn.Dense(64, name="up")(nn.LayerNorm(name="norm")(x))
        return x + nn.Dense(32, name="down")(nn.silu(h)), None


class Stack(nn.Module):
    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(100, 32, name="embed")(tokens)
        x, _ = nn.scan(nn.remat(Block, prevent_cse=False),
                       variable_axes={"params": 0},
                       split_rngs={"params": True}, length=3)(
                           name="layers")(x, None)
        return nn.Dense(100, name="lm_head")(x)


@pytest.fixture(scope="module")
def compiled():
    """A scanned, rematerialised stack under ``value_and_grad`` and
    Adafactor, compiled as the trainer compiles its step."""
    model, tx = Stack(), optax.adafactor(1e-3)
    tokens = jnp.zeros((4, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens)

    def _step_fn(params, opt, tokens):
        def loss(p):
            logits = model.apply(p, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens).mean()

        value, grads = jax.value_and_grad(loss)(params)
        with jax.named_scope("optimizer"):
            updates, opt = tx.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
        return params, opt, value

    return jax.jit(_step_fn).lower(params, tx.init(params), tokens).compile()


@pytest.fixture(scope="module")
def mapped(compiled):
    text = compiled.as_text()
    return text, *scope_map(text)


def test_the_four_passes_are_found(mapped):
    _, classes, ops = mapped
    seen = {classes[i][0] for i in ops.values()}
    assert seen <= set(PASSES)
    assert seen >= {"forward", "remat", "backward", "optimizer"}
    by_pass = {p: {c[1] for c in classes if c[0] == p} for p in PASSES}
    # the model's own path, JAX's wrappers taken out; what a scan does
    # between its layers has the top module's scope alone
    assert "Stack/layers/up" in by_pass["forward"]
    assert "Stack/layers/up" in by_pass["remat"]
    assert "Stack/layers/down" in by_pass["backward"]
    assert any(s.startswith("Stack/lm_head") for s in by_pass["backward"])
    assert by_pass["optimizer"] >= {"optimizer"}
    assert by_pass["none"] <= {""}


def test_a_product_reads_true_and_its_neighbour_false(mapped):
    _, classes, ops = mapped
    up = [c for c in classes if c[:2] == ["forward", "Stack/layers/up"]]
    norm = [c for c in classes if c[:2] == ["forward", "Stack/layers/norm"]]
    assert up and norm
    assert any(c[3] for c in up), up          # the Dense's dot
    assert not any(c[3] for c in norm), norm  # a norm holds none
    assert all(isinstance(c[3], bool) and c[2] for c in classes)


def test_every_key_is_an_instruction_of_the_text(mapped):
    text, _, ops = mapped
    names = set(re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", text, re.M))
    assert ops and set(ops) <= names
    # what runs inside a fusion has no event of its own, and is not kept
    fused = re.search(r"^%?(fused_computation[\w.]*) ", text, re.M).group(1)
    body = text.split(f"%{fused} (", 1)[1].split("\n}", 1)[0]
    inner = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", body, re.M)
    assert inner and not set(inner) & set(ops)
    # the loop's body is control flow: its instructions are
    assert any(n.startswith("fusion") or "fusion" in n for n in ops)


def test_the_line_survives_json_and_stays_small(mapped, compiled):
    _, classes, ops = mapped
    attrs = {"label": "train_step", **memory_attrs(compiled),
             "instructions": len(ops), "classes": classes, "ops": ops}
    line = json.dumps({"name": "step_program", "attrs": attrs})
    assert len(line) < LINE_LIMIT
    assert json.loads(line)["attrs"] == attrs
    assert all(0 <= i < len(classes) for i in ops.values())
    assert len({tuple(c) for c in classes}) == len(classes)   # interned
    assert attrs["argument_bytes"] > 0 and attrs["temp_bytes"] >= 0


@pytest.mark.parametrize("op_name, want", [
    ("jit(_step_fn)/jvp(Llama)/while/body/closed_call/layers/mlp/up_proj/"
     "dot_general", ("forward", "Llama/layers/mlp/up_proj")),
    ("jit(_step_fn)/transpose(jvp(Llama))/while/body/closed_call/checkpoint/"
     "rematted_computation/layers/mlp/jit(silu)/mul",
     ("remat", "Llama/layers/mlp")),
    ("jit(_step_fn)/transpose(jvp(Llama))/while/body/closed_call/checkpoint/"
     "layers/attn/o_proj/dot_general", ("backward", "Llama/layers/attn/o_proj")),
    ("jit(_step_fn)/transpose(jvp(Llama))/while/body/dynamic_update_slice",
     ("backward", "Llama")),
    ("jit(_step_fn)/jvp(lm_head)/while/body/closed_call/"
     "jit(take_along_axis)/gather", ("forward", "lm_head")),
    ("jit(_step_fn)/optimizer/jit(_where)/select_n",
     ("optimizer", "optimizer")),
    ("jit(_step_fn)/jvp(M)/cond/branch_1_fun/experts/dot_general",
     ("forward", "M/experts")),
    ("state.params['lm_head']['kernel']", ("none", "")),
    ("", ("none", "")),
])
def test_pass_and_scope_of_an_op_name(op_name, want):
    assert (pass_of(op_name), scope_of(op_name)) == want


HLO = """HloModule jit_f, is_scheduled=true

%fused_dus (p0: f32[4,8], p1: f32[8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %c = f32[1,8]{1,0} convolution(%p1, %p1), dim_labels=bf_io->bf
  %dus = f32[4,8]{1,0} dynamic-update-slice(%p0, %c, %p1)
  ROOT %bc = f32[4,8]{1,0:T(8,128)} bitcast(%dus)
}

%fused_late (q0: f32[4,8]) -> f32[4,8] {
  %q0 = f32[4,8]{1,0} parameter(0)
  %e = f32[4,8]{1,0} exponential(%q0), metadata={op_name="jit(f)/jvp(M)/while/body/layers/act/exp"}
  ROOT %cv = f32[4,8]{1,0} convert(%e)
}

%adder (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%body (t: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %t = (s32[], f32[4,8]{1,0}) parameter(0)
  %g = f32[4,8]{1,0} get-tuple-element(%t), index=1
  %zeros = f32[4,8]{1,0} broadcast(%g), dimensions={}
  %cs = (f32[4,8]{1,0}, f32[4,8]{1,0:S(1)}, u32[]) copy-start(%g)
  %cd = f32[4,8]{1,0:S(1)} copy-done(%cs)
  %fusion.9 = f32[4,8]{1,0} fusion(%zeros), kind=kLoop, calls=%fused_late
  %fusion.7 = f32[4,8]{1,0:T(8,128)} fusion(%cd, %g), kind=kLoop, calls=%fused_dus, metadata={op_name="jit(f)/transpose(jvp(M))/while/body/dynamic_update_slice;jit(f)/other/add" stack_frame_id=3}
  %r = f32[] reduce(%fusion.7, %g), dimensions={0,1}, to_apply=%adder
  ROOT %out = (s32[], f32[4,8]{1,0}) tuple(%g, %fusion.7, %fusion.9)
}

%cond (t: (s32[], f32[4,8])) -> pred[] {
  %t.1 = (s32[], f32[4,8]{1,0}) parameter(0)
  ROOT %lt = pred[] compare(%t.1, %t.1), direction=LT
}

ENTRY %main (x: f32[4,8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0), metadata={op_name="x"}
  %w = (s32[], f32[4,8]{1,0}) while(%x), condition=%cond, body=%body
  ROOT %y = f32[4,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_a_fusion_root_is_seen_through_and_reducers_are_left_out():
    classes, ops = scope_map(HLO)
    # entry, loop body and condition; not the fused computations' own
    # instructions, nor the scalar reducer a ``to_apply`` names
    assert set(ops) == {"x", "w", "y", "t", "g", "zeros", "cs", "cd",
                        "fusion.9", "fusion.7", "r", "out", "t.1", "lt"}
    assert classes[ops["fusion.7"]] == ["backward", "M",
                                        "dynamic-update-slice", True]
    assert classes[ops["x"]][0] == "none"   # a parameter's name is no scope


def test_what_the_compiler_left_unnamed_borrows_a_name():
    classes, ops = scope_map(HLO)
    # a fusion made late, from the instruction nearest its root
    assert classes[ops["fusion.9"]] == ["forward", "M/layers/act", "convert",
                                        False]
    # a copy between memories and the wait for it, from what they feed;
    # a zero fill, from its first user: the opcode stays their own
    assert classes[ops["cd"]] == ["backward", "M", "copy-done", False]
    assert classes[ops["cs"]] == ["backward", "M", "copy-start", False]
    assert classes[ops["zeros"]] == ["forward", "M/layers/act", "broadcast",
                                     False]
    # a result nothing here uses, from its first named operand
    assert classes[ops["r"]] == ["backward", "M", "reduce", False]
    # nothing to borrow from: none
    assert {classes[ops[n]][0] for n in ("x", "w", "y", "t.1", "lt")} == {
        "none"}


# -- the trainer ----------------------------------------------------------------

def _trainer():
    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.parallel.presets import dense_rules
    from tpucfn.train.trainer import Trainer

    mesh = build_mesh(MeshSpec.for_devices(jax.device_count()))

    def init_fn(rng):
        return {"w": jax.random.normal(rng, (4, 4))}, {}

    def loss_fn(params, mstate, batch, rng):
        return ((params["w"] @ batch["x"].T) ** 2).mean(), ({}, mstate)

    return Trainer(mesh, dense_rules(fsdp=False), loss_fn,
                   optax.adafactor(0.1), init_fn, eval_loss_fn=loss_fn)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Four steps at one batch shape, a fifth at another, and an eval, through
    TrainerObs; the trace file's rows."""
    from tpucfn.obs import MetricRegistry, Tracer
    from tpucfn.train.trainer import TrainerObs

    d = tmp_path_factory.mktemp("trace")
    tracer = Tracer(d, host_id=0, role="trainer")
    obs = TrainerObs(MetricRegistry(), tracer)
    tr = _trainer()
    tr.on_program = obs.record_program
    state = tr.init(jax.random.key(0))
    for i, rows in enumerate((8, 8, 8, 8, 16)):
        with obs.step(i + 1) as mark:
            state, _ = tr.step(state, {"x": np.ones((rows, 4), np.float32)})
            mark.dispatched()
    tr.eval_step(state, {"x": np.ones((8, 4), np.float32)})
    lowered = tr._jit_step.lower(tr.abstract_state(), {
        "x": jax.ShapeDtypeStruct((8, 4), np.float32,
                                  sharding=tr.batch_sharding())})
    tracer.close()
    rows = [json.loads(ln) for ln in
            (d / "trace-trainer-host000.jsonl").read_text().splitlines()]
    return rows, lowered, tr


def test_four_steps_write_one_program_whose_children_sum_to_it(traced_run):
    rows, _, _ = traced_run
    programs = [r for r in rows if r["name"] == "step_program"
                and r["attrs"]["label"] == "train_step"]
    first = programs[0]
    assert first["trace_id"] == 1 and len(programs) == 2
    assert not [r for r in programs if r["trace_id"] in (2, 3, 4)]
    step = next(r for r in rows if r["name"] == "step" and r["trace_id"] == 1)
    assert first["parent_id"] == step["span_id"]
    assert step["start"] <= first["start"] and first["dur_s"] <= step["dur_s"]
    kids = [r for r in rows if r["parent_id"] == first["span_id"]]
    assert [k["name"] for k in kids] == ["program_lower", "program_compile",
                                         "program_scopes"]
    assert sum(k["dur_s"] for k in kids) == pytest.approx(first["dur_s"],
                                                          abs=1e-9)
    assert kids[0]["start"] == first["start"]
    assert next(k for k in kids if k["name"] == "program_scopes")["dur_s"] < 1
    a = first["attrs"]
    assert a["module"] == "jit__step_fn" and a["outcome"] in ("hit", "miss")
    assert a["instructions"] == len(a["ops"]) > 0
    assert {"argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
            "code_bytes"} <= set(a)
    assert not [r for r in rows if r["name"] == "compile_cache"]


def test_a_second_batch_shape_and_an_eval_write_their_own(traced_run):
    rows, _, tr = traced_run
    programs = [(r["attrs"]["label"], r["trace_id"], r["parent_id"])
                for r in rows if r["name"] == "step_program"]
    step5 = next(r for r in rows if r["name"] == "step" and r["trace_id"] == 5)
    # the eval compiled outside any step: no parent, the last step's number
    assert programs == [("train_step", 1, programs[0][2]),
                        ("train_step", 5, step5["span_id"]),
                        ("train_eval", 5, None)]
    assert tr._jit_step._cache_size() >= 2


def test_the_step_holder_still_lowers(traced_run):
    _, lowered, _ = traced_run
    assert "func.func public @main" in lowered.as_text()


WORKER = """
import json, sys
import jax, numpy as np, optax
from tpucfn.obs import MetricRegistry, Tracer, enable_compile_cache
enable_compile_cache(sys.argv[1], min_compile_time_s=0)
sys.path.insert(0, sys.argv[3])
from test_step_program import _trainer
from tpucfn.train.trainer import TrainerObs
tracer = Tracer(sys.argv[2], host_id=0, role="trainer")
obs = TrainerObs(MetricRegistry(), tracer)
tr = _trainer()
tr.on_program = obs.record_program
state = tr.init(jax.random.key(0))
with obs.step(1):
    tr.step(state, {"x": np.ones((8, 4), np.float32)})
tracer.close()
"""


def test_outcome_reads_miss_then_hit_over_two_processes(tmp_path):
    """Two processes sharing a cache directory: the first compiles, the
    second is served, each by JAX's own account of its compile."""
    env = {**os.environ, "PYTHONPATH": str(ROOT)}
    outcomes = []
    for n in (1, 2):
        subprocess.run(
            [sys.executable, "-c", WORKER, str(tmp_path / "xla"),
             str(tmp_path / f"trace{n}"), str(ROOT / "tests")],
            check=True, env=env, timeout=120)
        rows = [json.loads(ln) for ln in
                (tmp_path / f"trace{n}" / "trace-trainer-host000.jsonl")
                .read_text().splitlines()]
        program = next(r for r in rows if r["name"] == "step_program")
        outcomes.append(program["attrs"]["outcome"])
        # the map is there after a hit too
        assert program["attrs"]["instructions"] > 0
    assert outcomes == ["miss", "hit"]
