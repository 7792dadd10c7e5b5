"""Worker for the fleet warm-start ft drill (ISSUE 13 acceptance).

Each incarnation: configure the compile-cache client from the launcher
env (with a per-process-unique LOCAL store, so a relaunch cannot
store-hit and must go through the FLEET server), run one warm-jitted
step under TrainerObs + GoodputLedger, append the computed value to a
results file, and crash (rc 1) on the first attempt so the coordinator
gang-restarts.  The test then asserts the relaunched incarnation's
ledger window charged ``compile_fetched`` (not ``compile``) and the
two attempts' values are bit-identical.
"""

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    work = Path(os.environ["CC_DRILL_DIR"])
    host = int(os.environ.get("TPUCFN_HOST_ID", "0") or 0)
    # per-incarnation local store: a relaunch must FETCH from the fleet
    # server, never shortcut through the shared local artifact dir
    os.environ["TPUCFN_COMPILE_CACHE_DIR"] = str(
        work / f"store-{os.getpid()}")

    import numpy as np

    import jax
    import jax.numpy as jnp

    from tpucfn.compilecache import configure_from_env
    from tpucfn.compilecache.jit import maybe_warm
    from tpucfn.obs.goodput import GoodputLedger
    from tpucfn.obs.registry import MetricRegistry
    from tpucfn.train.trainer import TrainerObs

    client = configure_from_env()
    assert client is not None, "drill env must carry the cache fan-out"

    def fn(x):
        h = x
        for _ in range(8):
            h = jnp.tanh(h @ h.T) @ h
        return h.sum()

    step = maybe_warm(jax.jit(fn), label="ft_drill")
    ledger = GoodputLedger(work / "goodput", host)
    obs = TrainerObs(MetricRegistry(), ledger=ledger)
    step.on_program = obs.record_program  # the bucket follows the outcome
    x = np.full((16, 16), 0.01, np.float32)
    with obs.step(1):
        out = float(step(x))
    ledger.close()

    with open(work / f"results-host{host}.jsonl", "a") as f:
        f.write(json.dumps({"pid": os.getpid(), "value": out,
                            "outcome": client.last_outcome}) + "\n")

    flag = work / f"crashed-{host}"
    if not flag.exists():
        flag.write_text(str(os.getpid()))
        return 1  # first incarnation crashes: the coordinator restarts
    return 0


if __name__ == "__main__":
    sys.exit(main())
