"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the pod
axis is the inter-pod data-parallel dimension (DCN-connected in a real
deployment; gradient all-reduce crosses it once per step).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(devices=None):
    """``devices`` (default: every device the process sees) as a
    (data, model) mesh with model = 1: the MPSL client axis and FSDP both
    run over ``data``."""
    devices = list(devices) if devices is not None else jax.devices()
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(auto, auto), devices=devices)


# TPU v5e-class hardware constants for the roofline (per chip / per link)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
