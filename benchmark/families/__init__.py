"""Model families, one module each, found by the ``family`` of a
configuration's file: ``benchmark/families/<family>.py`` gives

- ``reference``: the plain reference (``param_spec``, ``state_spec``,
  ``loss``), which imports nothing of the program;
- ``build(config, mix, mesh, init_fn)``: the program's ``Trainer`` for the
  configuration, built the way the example builds it, and the items a step
  holds; the program is imported inside it, not by the module;
- ``step_flops(model, shape)``: the operations one optimizer step needs.

A new family is a new module here beside its data files; nothing that is
there changes.
"""

from benchmark import by_name


def load(name: str):
    return by_name("families", name, "family")
