"""chip_smoke.py's control flow off the chip, and the compile-cache rule.

Everything runs in a child with an explicit environment: conftest.py
strips every ``JAX_*`` variable from this process, and the cache rule is
about exactly those."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "TPUCFN_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    env.update(extra)
    return env


def _smoke(*args, timeout=900):
    return subprocess.run([sys.executable, str(REPO / "chip_smoke.py"), *args],
                          env=_child_env(), capture_output=True, text=True,
                          timeout=timeout, cwd=REPO)


def test_rehearsal_runs_every_one_chip_phase_on_the_cpu():
    r = _smoke("--rehearse")
    assert r.returncode == 0, r.stderr[-3000:]
    rows = [json.loads(line) for line in r.stdout.splitlines()
            if line.startswith("{")]
    assert [row["phase"] for row in rows[:-1]] == [
        "start", "kernel", "train_llama", "serve", "train_example"]
    assert rows[0]["rehearsal"] is True
    # the true platform is printed, never dressed up as the chip
    assert rows[-1] == {"ok": True, "device": rows[0]["device"]}
    assert rows[-1]["device"]["platform"] == "cpu"
    assert r.stdout.splitlines()[-1] == json.dumps(rows[-1])
    for row in rows[1:-1]:
        assert row["seconds"] > 0 and row["compile_seconds"] >= 0
    assert rows[3]["compile_counts"]["decode"] == 1
    assert rows[4]["finalized_checkpoints"][-1] == 4


def test_without_the_rehearsal_option_the_cpu_is_refused_before_any_phase():
    r = _smoke(timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "no TPU" in r.stderr


_CACHE_RULE = """
import json, os, sys
import jax
updates = []
_update = jax.config.update
def spy(name, value):
    updates.append(name)
    return _update(name, value)
jax.config.update = spy
from tpucfn.obs import enable_compile_cache
from tpucfn.compilecache.store import default_store_dir
returned = enable_compile_cache(*sys.argv[1:])
print(json.dumps({"returned": returned,
                  "jax_dir": jax.config.jax_compilation_cache_dir,
                  "set_dir_in_code": "jax_compilation_cache_dir" in updates,
                  "store_dir": default_store_dir()}))
"""


@pytest.mark.parametrize("case", ["env_set", "env_unset", "explicit_wins"])
def test_compile_cache_rule(case, tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    explicit = str(tmp_path / "explicit")
    fixed = str(REPO / ".cache" / "xla")
    env = _child_env(**({} if case == "env_unset"
                        else {"JAX_COMPILATION_CACHE_DIR": placed}))
    argv = [explicit] if case == "explicit_wins" else []
    r = subprocess.run([sys.executable, "-c", _CACHE_RULE, *argv], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.splitlines()[-1])
    want_dir, set_in_code = {
        # placed from outside: that directory, and the code sets no other
        "env_set": (placed, False),
        # a fixed path inside the checkout — never /tmp, a pid or a time
        "env_unset": (fixed, True),
        "explicit_wins": (explicit, True),
    }[case]
    assert got["returned"] == got["jax_dir"] == want_dir
    assert got["set_dir_in_code"] is set_in_code
    # the artifact store sits beside the cache the rule names
    rule_dir = fixed if case == "env_unset" else placed
    assert got["store_dir"] == rule_dir + "_artifacts"
