"""Plain float32 ``jax.numpy`` references of the benchmark's models.

They import nothing of the program under test: weights come from
``init.make_params`` (seeded, in the layout the program consumes), tables are
quantised by ``common.quantize_rows`` and every matmul runs at the
precision the caller sets (``highest`` for the reference).
"""
