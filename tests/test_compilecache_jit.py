"""Fleet warm-start plane, jax half (ISSUE 13): fingerprinting, the
``maybe_warm`` wrapper, the pinned byte-identical default, the trainer
integration, and the goodput bucket split."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpucfn.compilecache.jit import (  # noqa: E402
    WarmJit,
    configure_client_from_env,
    get_default_client,
    lowered_fingerprint,
    maybe_warm,
    set_default_client,
)
from tpucfn.compilecache.service import CompileCacheClient  # noqa: E402
from tpucfn.compilecache.store import ArtifactStore  # noqa: E402


@pytest.fixture(autouse=True)
def _no_default_client():
    """Every test starts and ends with no process-default client —
    the global must never leak across the suite."""
    set_default_client(None)
    yield
    set_default_client(None)


def _client(tmp_path, **kw):
    from tpucfn.compilecache.jit import runtime_identity

    kind, ver = runtime_identity()
    return CompileCacheClient(
        ArtifactStore(tmp_path / "art", device_kind=kind, jax_version=ver),
        [], device_kind=kind, jax_version=ver, **kw)


# -- the pinned default -----------------------------------------------------

def test_maybe_warm_without_client_is_identity():
    """TPUCFN_COMPILE_CACHE_{ADDRS,DIR} unset ⇒ maybe_warm returns the
    jitted callable ITSELF — byte-identical behavior, pinned."""
    jitted = jax.jit(lambda x: x * 2)
    assert maybe_warm(jitted, label="x") is jitted


def test_configure_from_env_absent_installs_nothing():
    assert configure_client_from_env(env={}) is None
    assert get_default_client() is None


def test_trainer_step_is_the_one_path_without_client():
    """No client configured: the trainer's step is the same holder with
    nothing in front of its compile — it lowers and compiles once, calls
    the ``Compiled`` from then on, and says what it compiled."""
    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.parallel.presets import dense_rules
    from tpucfn.train.trainer import Trainer

    import optax

    mesh = build_mesh(MeshSpec.for_devices(jax.device_count()))

    def init_fn(rng):
        return {"w": jnp.ones((4, 4))}, {}

    def loss_fn(params, mstate, batch, rng):
        return (batch["x"] @ params["w"]).sum(), ({}, mstate)

    tr = Trainer(mesh, dense_rules(fsdp=False), loss_fn,
                 optax.sgd(0.1), init_fn)
    heard = []
    tr.on_program = lambda compiled, **about: heard.append(about)
    state = tr.init(jax.random.key(0))
    for _ in range(3):
        state, _ = tr.step(state, {"x": np.ones((8, 4), np.float32)})
    assert isinstance(tr._jit_step, WarmJit) and tr._jit_step.client is None
    assert tr._jit_step._fast is not None and not tr._jit_step._disabled
    assert [h["label"] for h in heard] == ["train_step"]
    assert heard[0]["outcome"] in ("hit", "miss")
    assert heard[0]["lower_start"] <= heard[0]["compile_start"] \
        <= heard[0]["compile_end"]


def test_without_client_a_compile_error_reaches_the_caller():
    """There is no artifact plane to degrade from: what the compiler (or
    the lowering) raises is the caller's, once, not a second attempt
    through the plain jit."""
    calls = []

    def bad(x):
        calls.append(1)
        raise RuntimeError("cannot trace this")

    w = WarmJit(jax.jit(bad), None, label="bad")
    with pytest.raises(RuntimeError, match="cannot trace this"):
        w(np.ones((2,), np.float32))
    assert calls == [1] and not w._disabled


# -- fingerprinting ---------------------------------------------------------

def test_fingerprint_stable_and_shape_sensitive():
    fn = jax.jit(lambda x: jnp.sin(x).sum())
    a = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    b = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    k1 = lowered_fingerprint(fn.lower(a), label="t")
    assert k1 == lowered_fingerprint(fn.lower(a), label="t")
    assert k1 != lowered_fingerprint(fn.lower(b), label="t")
    # a different program with the same avals keys differently
    other = jax.jit(lambda x: jnp.cos(x).sum())
    assert k1 != lowered_fingerprint(other.lower(a), label="t")


# -- the warm path ----------------------------------------------------------

def test_warm_roundtrip_compile_then_store_hit(tmp_path):
    fn = lambda x: jnp.tanh(x @ x.T).sum()  # noqa: E731
    x = np.ones((16, 16), np.float32)

    c1 = _client(tmp_path)
    w1 = maybe_warm(jax.jit(fn), label="p", client=c1)
    r1 = w1(x)
    assert c1.last_outcome == "compile"
    assert c1.compiles_c.value == 1

    # a second client over the same store (≈ a relaunched process)
    c2 = _client(tmp_path)
    w2 = maybe_warm(jax.jit(fn), label="p", client=c2)
    r2 = w2(x)
    assert c2.last_outcome == "store"
    assert c2.compiles_c.value == 0
    assert np.array_equal(np.asarray(r1), np.asarray(r2))  # bit-identical


def test_warm_jit_memoizes_per_shape_bucket(tmp_path):
    c = _client(tmp_path)
    calls = []
    real = c.get_or_compile

    def spy(key, compile_fn, **kw):
        calls.append(key)
        return real(key, compile_fn, **kw)

    c.get_or_compile = spy
    w = maybe_warm(jax.jit(lambda x: x.sum()), label="b", client=c)
    w(np.ones((4,), np.float32))
    w(np.ones((4,), np.float32))   # same bucket: memoized, no re-key
    w(np.ones((8,), np.float32))   # new bucket
    assert len(calls) == 2 and calls[0] != calls[1]


def test_warm_path_failure_degrades_to_plain_jit(tmp_path):
    c = _client(tmp_path)

    def boom(*a, **k):
        raise RuntimeError("artifact plane down")

    c.get_or_compile = boom
    w = maybe_warm(jax.jit(lambda x: x * 3), label="d", client=c)
    out = w(np.ones((2,), np.float32))
    assert np.array_equal(np.asarray(out), np.full((2,), 3.0))
    assert w._disabled  # permanent, no per-call retry storm


def test_trainer_trajectory_bit_identical_with_cache(tmp_path):
    """The acceptance pin: the same trainer run, cache off vs cache on
    (cold store, then warm store), produces bit-identical states."""
    import optax

    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.parallel.presets import dense_rules
    from tpucfn.train.trainer import Trainer

    mesh = build_mesh(MeshSpec.for_devices(jax.device_count()))

    def init_fn(rng):
        return {"w": jax.random.normal(rng, (4, 4))}, {}

    def loss_fn(params, mstate, batch, rng):
        return ((params["w"] @ batch["x"].T) ** 2).mean(), ({}, mstate)

    def run(client) -> list[float]:
        set_default_client(client)
        try:
            tr = Trainer(mesh, dense_rules(fsdp=False), loss_fn,
                         optax.sgd(0.1), init_fn)
            state = tr.init(jax.random.key(7))
            losses = []
            for i in range(3):
                batch = {"x": np.full((8, 4), 1.0 + i, np.float32)}
                state, m = tr.step(state, batch)
                losses.append(float(m["loss"]))
            return losses
        finally:
            set_default_client(None)

    baseline = run(None)
    cold = run(_client(tmp_path))      # compiles + publishes
    warm_client = _client(tmp_path)
    warm = run(warm_client)            # served from the artifact store
    assert baseline == cold == warm
    assert warm_client.last_outcome == "store"


# -- probe / goodput split --------------------------------------------------

@pytest.mark.parametrize("fleet, bucket", [
    ("compile", "compile"), ("store", "compile_cached"),
    ("fetch", "compile_fetched")])
def test_client_verdict_reaches_the_ledger_bucket(tmp_path, fleet, bucket):
    """End-to-end bucket split: the client's verdict becomes the program's
    ``outcome``, TrainerObs charges the first step's bucket from it, and
    the merge reports the column."""
    from tpucfn.obs.goodput import (GoodputLedger, REPORT_BUCKETS,
                                    host_goodput, read_goodput_dir)
    from tpucfn.obs.registry import MetricRegistry
    from tpucfn.train.trainer import TrainerObs

    assert bucket in REPORT_BUCKETS
    c = _client(tmp_path)
    real = c.get_or_compile
    # the artifact came from where ``fleet`` says (a peer, the local store,
    # the compiler): the program is the same either way
    c.get_or_compile = lambda key, fn, **kw: (real(key, fn, **kw)[0], fleet)
    ledger = GoodputLedger(tmp_path / "gp", 0)
    obs = TrainerObs(MetricRegistry(), ledger=ledger)
    w = WarmJit(jax.jit(lambda x: x.sum()), c, label="probe",
                on_program=obs.record_program)
    with obs.step(1):
        w(np.ones((4,), np.float32))
    with obs.step(2):
        w(np.ones((4,), np.float32))
    ledger.close()
    by_host, _ = read_goodput_dir(tmp_path / "gp")
    rep = host_goodput(by_host[0])
    assert rep["buckets"][bucket] > 0
    assert all(rep["buckets"][b] == 0 for b in
               ("compile", "compile_cached", "compile_fetched")
               if b != bucket)


def test_warm_jit_fast_path_single_bucket(tmp_path):
    """Review-pass pin: in steady state (one shape bucket — the
    trainer's every-step case) dispatch skips the per-call signature
    walk; a NEW bucket still resolves correctly through the slow path,
    which then retires the fast path for this multi-bucket wrapper."""
    c = _client(tmp_path)
    w = maybe_warm(jax.jit(lambda x: x.sum()), label="fast", client=c)
    r4 = w(np.ones((4,), np.float32))
    assert w._fast is not None  # armed after the single bucket resolved
    sig_calls = []
    import tpucfn.compilecache.jit as ccjit

    real_sig = ccjit._avals_signature
    ccjit._avals_signature = lambda a, k: (sig_calls.append(1),
                                           real_sig(a, k))[1]
    try:
        assert float(w(np.ones((4,), np.float32))) == float(r4)
        assert sig_calls == []  # steady state: no signature walk
        # a different bucket routes through the slow path and computes
        # the right answer (the AOT executable refuses the avals
        # mismatch BEFORE executing — donation-safe)
        assert float(w(np.ones((8,), np.float32))) == 8.0
        assert sig_calls and w._fast is None  # multi-bucket: retired
        sig_calls.clear()
        assert float(w(np.ones((4,), np.float32))) == float(r4)
        assert sig_calls  # both buckets now use the signature path
    finally:
        ccjit._avals_signature = real_sig


def test_warm_jit_cache_size_duck_type(tmp_path):
    """Second-review pin: the jit_cache_programs gauge reads
    ``_cache_size()`` off whatever jit_sources returns — a WarmJit must
    answer with its resolved-bucket count, not AttributeError-into-0."""
    c = _client(tmp_path)
    w = maybe_warm(jax.jit(lambda x: x.sum()), label="gauge", client=c)
    assert w._cache_size() == 0
    w(np.ones((4,), np.float32))
    assert w._cache_size() == 1
    w(np.ones((8,), np.float32))
    assert w._cache_size() == 2
