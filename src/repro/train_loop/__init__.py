"""PS-centric end-to-end training (§3.2, §4): real model steps whose every
projection GEMM — forward and backward — executes on the edge fleet through
the :class:`~repro.api.CleaveRuntime` executors, while the parameter server
hosts everything else (norms, softmax, activations, loss, AdamW, optimizer
state).

Layout
------
``hook``        the pluggable GEMM hook that ``models.layers.pdot`` consults
                (dependency-free; safe to import from model code).
``fleet_gemm``  :class:`FleetGemmSession` — a differentiable ``fleet_dot``
                (``jax.custom_vjp`` over concrete operands) that runs each
                intercepted GEMM, and its two backward mirrors
                (dA = dO·Bᵀ, dW = Aᵀ·dO), through the session runtime's
                numpy/jax fleet executors.
``train_step``  :func:`make_fleet_train_step` — one forward+backward+AdamW
                step with PS-hosted non-GEMM ops, fleet metrics (measured vs
                ``engine.price_plan`` predicted makespan), and mid-step
                failure injection that exercises ``churn.recover``.
``multi_ps``    :class:`MultiPSTrainSession` — K parameter-server islands
                (``api.ShardedFleet``), each a full ``FleetTrainSession``
                over its own subfleet, synced every H inner steps by the
                sharded DiLoCo outer loop (``optim.diloco``); PS failures
                evict whole islands (docs/TRAINING.md).

The package ``__init__`` is lazy (PEP 562) so that ``models.layers`` can
import :mod:`repro.train_loop.hook` without dragging the runtime stack into
every model import.
"""
from __future__ import annotations

_LAZY = {
    "FleetGemmSession": "repro.train_loop.fleet_gemm",
    "GemmRecord": "repro.train_loop.fleet_gemm",
    "FleetStepReport": "repro.train_loop.train_step",
    "FleetTrainSession": "repro.train_loop.train_step",
    "make_fleet_train_step": "repro.train_loop.train_step",
    "price_request": "repro.train_loop.train_step",
    "MultiPSState": "repro.train_loop.multi_ps",
    "MultiPSStepReport": "repro.train_loop.multi_ps",
    "MultiPSTrainSession": "repro.train_loop.multi_ps",
}

__all__ = sorted(_LAZY) + ["hook"]


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
