"""Backend-aware Pallas lowering resolution.

Every Pallas kernel entry point takes ``interpret: bool | None``.  ``True``
runs the kernel in interpret mode (pure XLA emulation of the grid — the
only mode that works on CPU), ``False`` lowers natively through Mosaic.
``None`` — the default everywhere — defers the decision to this module:
the plan's calibrated ``lowering`` knob if one is threaded through, else
:data:`repro.tuning.defaults.DEFAULT_LOWERING` resolved per backend.

Two rules keep the device visible:

* On a TPU backend the resolution is always ``"native"``.  Interpret mode
  there would run the kernel as slow XLA emulation on the chip while
  reporting success, so asking for it is an error.
* Only the kernels in :data:`NATIVE_KERNELS` lower through Mosaic.  Asking
  any other kernel for a native lowering raises here, before tracing, with
  the kernel's name — never a Mosaic error in the middle of a serving flush.

This is the one place that inspects ``jax.default_backend()``, so the
kernels, the core edgeMap, and the planner all agree on what ``None``
means and the serving cache can key executables by the *resolved* value.
"""
from __future__ import annotations

import jax

from ..tuning.defaults import DEFAULT_LOWERING

LOWERINGS = ("auto", "native", "interpret")

# Kernels that lower through Mosaic, each compiled for a v5e chip by
# tests/test_tpu_compile.py.  The graph kernels outside this set gather
# from a VMEM-resident vector at data-dependent indices or stream 1-D (TB,)
# blocks, both of which Mosaic refuses; no kernel outside it has been
# compiled for the chip.  They run in interpret mode off the TPU only.
NATIVE_KERNELS = frozenset({"compressed_chunked_decode"})


def native_lowering_supported() -> bool:
    """True when this process can lower Pallas kernels natively (Mosaic).

    Native lowering needs a TPU backend; on CPU/GPU hosts the kernels run
    in interpret mode.  (Pallas-on-GPU Triton lowering is not wired into
    these kernels' BlockSpecs, so GPU counts as unsupported here.)
    """
    return jax.default_backend() == "tpu"


def resolve_lowering(lowering: str | None = None, *, kernel: str | None = None) -> str:
    """Collapse a lowering knob to ``"native"`` or ``"interpret"``.

    ``None`` and ``"auto"`` pick native lowering exactly when
    :func:`native_lowering_supported` says the backend can compile it.  An
    explicit ``"native"`` passes through off the TPU too (it fails at
    compile time, the right outcome for an explicit override); an explicit
    ``"interpret"`` on a TPU backend raises ``ValueError``.  With
    ``kernel``, a native resolution for a kernel outside
    :data:`NATIVE_KERNELS` raises ``NotImplementedError``.
    """
    if lowering is None:
        lowering = DEFAULT_LOWERING
    if lowering not in LOWERINGS:
        raise ValueError(f"lowering must be one of {LOWERINGS}, got {lowering!r}")
    on_tpu = native_lowering_supported()
    if lowering == "auto":
        lowering = "native" if on_tpu else "interpret"
    if on_tpu and lowering == "interpret":
        raise ValueError(
            "interpret-mode Pallas on a TPU backend would emulate the kernels "
            "in XLA instead of running them on the chip; use lowering='native'"
        )
    if lowering == "native" and kernel is not None and kernel not in NATIVE_KERNELS:
        raise NotImplementedError(
            f"Pallas kernel {kernel!r} has no Mosaic lowering (kernels that "
            f"lower: {sorted(NATIVE_KERNELS)}); run it in interpret mode on a "
            "CPU backend"
        )
    return lowering


def resolve_interpret(
    interpret: bool | None = None,
    lowering: str | None = None,
    *,
    kernel: str | None = None,
) -> bool:
    """The ``interpret=`` flag a ``pl.pallas_call`` should actually get.

    An explicit bool wins over the ``lowering`` knob
    (``"auto"``/``"native"``/``"interpret"``, default
    :data:`~repro.tuning.defaults.DEFAULT_LOWERING`); either way the result
    passes the checks of :func:`resolve_lowering` for ``kernel``.
    """
    if interpret is not None:
        lowering = "interpret" if interpret else "native"
    return resolve_lowering(lowering, kernel=kernel) == "interpret"
