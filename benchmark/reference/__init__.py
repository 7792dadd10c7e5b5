"""Plain references: float32 ``jax.numpy`` at ``highest`` precision, no
kernels, no import of the program.  A family's module
(``benchmark/families/<family>.py``) names its reference here."""
