"""Pallas kernels for the relaxation hot loop (plus two gather-reduce
primitives, ``embed_bag`` and ``spmm``, that the SSSP engines do not use).

Every kernel ships three artifacts:
  * ``<name>/<name>.py`` — the pl.pallas_call + BlockSpec kernel;
  * ``<name>/ops.py``    — the jitted public wrapper (+ shape plumbing);
  * ``<name>/ref.py``    — a pure-jnp oracle, used by tests and by the
    engines, whose default waves are plain XLA.

No kernel is on a default path.  On the CPU the kernels run in interpret
mode (the kernel body executed as traced jax ops), which is how the test
suite validates them.  On a TPU only ``relax/relax.py:ellpack_relax``
compiles; Mosaic refuses ``relax/fused.py`` and ``relax/gather.py``, and
asking an engine for either on a TPU raises at construction
(``relax/config.py``, DESIGN.md §2.7).
"""
