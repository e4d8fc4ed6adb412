"""Where the relax kernels run: the one platform decision of the engines.

The Pallas kernels compile through Mosaic only on TPU; everywhere else they
run in interpret mode (the kernel body executed as traced jax ops), which is
how the CPU test suite exercises them.  ``on_tpu()`` probes the platform
once per process; every engine, backend and kernel entry point asks it here
instead of probing ``jax.default_backend()`` on its own.

No kernel is on a default path: the engines run the XLA waves unless a
config asks for a kernel explicitly (``ell_use_kernel``, ``sliced_fused``,
``frontier_kernel``).  Compiled for a described TPU v5e with jax 0.9.0
(tests/test_chip_compile.py), the kernels stand as follows:

* ``relax.ellpack_relax`` compiles (the gather runs in XLA before it);
* ``fused.fused_sliced_relax`` and ``gather.gathered_rows_relax`` are
  refused by Mosaic.  ``REFUSED_ON_TPU`` keeps the compiler's message for
  each, and ``check_kernel_request`` raises it when a config asks for one
  of them on a TPU — nothing falls back to interpret mode or to the jnp
  reference in silence.
"""
from __future__ import annotations

import jax

_ON_TPU: bool | None = None

# config knob -> why Mosaic refuses the kernel it selects (jax 0.9.0, v5e)
REFUSED_ON_TPU = {
    "sliced_fused": (
        "kernels/relax/fused.py:fused_sliced_relax does not compile for "
        "TPU: Mosaic raises 'NotImplementedError: Only 2D gather is "
        "supported' on the in-kernel jnp.take over the whole offer vector, "
        "and its overflow-lane fold needs scatter-min ('Unimplemented "
        "primitive in Pallas TPU lowering: scatter-min')"),
    "frontier_kernel": (
        "kernels/relax/gather.py:gathered_rows_relax does not compile for "
        "TPU: 'Unimplemented primitive in Pallas TPU lowering for "
        "KernelType.TC: scatter-min'"),
}


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (probed once per process;
    ``jax.default_backend()`` initializes the backend)."""
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU


def default_interpret() -> bool:
    """Interpret mode everywhere except TPU."""
    return not on_tpu()


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> the platform default; an explicit bool wins (tests force
    ``interpret=True`` regardless of platform)."""
    return default_interpret() if interpret is None else bool(interpret)


def check_kernel_request(cfg) -> None:
    """Raise at engine construction when ``cfg`` asks for a kernel that
    Mosaic refuses and the engine would run on a TPU."""
    if not on_tpu():
        return
    for knob, reason in REFUSED_ON_TPU.items():
        if getattr(cfg, knob, False):
            raise ValueError(f"{knob}=True cannot run on TPU: {reason}")
