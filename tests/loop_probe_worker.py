"""Runs one example's ``main()`` in this process and prints, as its last line,
what only the process itself can see of ``run_train_loop``: every message
``jax_log_compiles`` wrote about ``_step_fn``, and the threads alive once the
loop has returned that were not there before it started.

    python loop_probe_worker.py <examples/script.py> [the example's arguments]
"""

import importlib.util
import json
import logging
import sys
import threading

import jax


def main() -> int:
    script, argv = sys.argv[1], sys.argv[2:]
    messages: list[str] = []

    class Keep(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    jax.config.update("jax_log_compiles", True)
    logging.getLogger("jax").addHandler(Keep())
    spec = importlib.util.spec_from_file_location("probed_example", script)
    example = importlib.util.module_from_spec(spec)
    sys.argv = [script, *argv]
    spec.loader.exec_module(example)
    before = set(threading.enumerate())
    rc = example.main()
    print(json.dumps({
        "rc": rc,
        "step_fn_messages": [m[:60] for m in messages if "_step_fn" in m],
        "threads_left": sorted(t.name for t in threading.enumerate()
                               if t not in before)}), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
