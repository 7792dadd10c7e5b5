"""Worker for the multi-process rendezvous test (not a pytest module).

Launched by tests/test_multiprocess.py via the Launcher: joins the
jax.distributed rendezvous from the env contract, builds a global mesh
over both processes' CPU devices, and runs a cross-process reduction.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main() -> int:
    from tpucfn.launch import initialize_runtime
    from tpucfn.mesh import MeshSpec, build_mesh
    from tpucfn.parallel import shard_batch

    contract = initialize_runtime()
    assert contract is not None, "no cluster env"
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 4  # 2 procs x 2 fake devices

    mesh = build_mesh(MeshSpec(data=4))
    # each process contributes rows of value (process_index + 1)
    local = np.full((2, 3), jax.process_index() + 1.0, np.float32)
    batch = shard_batch(mesh, {"x": local})
    total = jax.jit(lambda b: jnp.sum(b["x"]))(batch)
    expect = (1 + 2) * 2 * 3
    assert float(total) == expect, (float(total), expect)
    print(f"RENDEZVOUS_OK rank={jax.process_index()} total={float(total)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
