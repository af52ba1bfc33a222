"""Custom compute kernels (Pallas) + the autotune dogfood loop.

Each subpackage ships ``kernel.py`` (the Pallas body), ``ops.py`` (the
jit'd model-layout wrapper) and ``ref.py`` (the jnp oracle).  Tiling
parameters (block sizes, chunk widths) are exposed as keyword knobs on
the ops wrappers; :mod:`repro.kernels.autotune` turns each wrapper's
``autotune_space()``/``autotune_bench()`` pair into a Sapphire search
problem, so the tuner tunes its own kernels (ROADMAP's dogfood item).
"""

from __future__ import annotations

from typing import Optional


def tuning_compiler_params(num_warps: Optional[int] = None,
                           pipeline: Optional[int] = None,
                           interpret: bool = False,
                           vmem_limit_bytes: Optional[int] = None):
    """``pallas_call`` compiler params for the tunable scheduling knobs.

    ``num_warps``/``pipeline`` (pipeline depth → Triton ``num_stages``)
    only exist on the GPU lowering; on TPU the Mosaic pipeline is derived
    from the BlockSpecs and in interpret mode there is no compiler at
    all — those knobs are *inert* off-GPU and the autotune space stays
    portable.  ``vmem_limit_bytes`` raises Mosaic's scoped VMEM limit for
    a kernel whose tiles need more than the default; it applies to every
    non-interpreted compile off the GPU, which is a compile for the TPU
    (the chip need not be attached)."""
    import jax
    if interpret:
        return None
    if jax.default_backend() != "gpu":
        if not vmem_limit_bytes:
            return None
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit_bytes))
    params = {}
    if num_warps:
        params["num_warps"] = int(num_warps)
    if pipeline:
        params["num_stages"] = int(pipeline)
    return {"triton": params} if params else None


_AUTOTUNE_EXPORTS = ("KernelEvaluator", "kernel_bench", "kernel_space",
                     "tunable_kernels", "tune_kernel")


def __getattr__(name):
    # lazy: autotune imports the ops modules, which import this package
    if name in _AUTOTUNE_EXPORTS:
        from repro.kernels import autotune
        return getattr(autotune, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
