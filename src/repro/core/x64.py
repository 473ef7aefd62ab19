"""The float64 scope shared by the evaluator and the controllers.

The interval model (:mod:`repro.sim.memsys_jax`), the batched Lookahead
allocator (:mod:`repro.core.cache_controller_jax`) and the fused plant
(:mod:`repro.runtime.plant_jax`) run in float64 so they match their numpy
goldens.  Every device call on those paths enters :func:`x64_context`;
there is no float32 fallback.
"""
from __future__ import annotations

import jax


def x64_context():
    """Context manager enabling 64-bit types for the enclosed traces."""
    return jax.enable_x64(True)
