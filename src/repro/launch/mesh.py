"""The host mesh the trainer and the benchmark run on.

Defined as a function so importing this module never touches jax device
state.
"""
from __future__ import annotations

import jax


def make_host_mesh(devices=None):
    """``devices`` (default: every device the process sees) as a
    (data, model) mesh with model = 1: the MPSL client axis and FSDP both
    run over ``data``."""
    devices = list(devices) if devices is not None else jax.devices()
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(auto, auto), devices=devices)
