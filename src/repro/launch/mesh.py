"""Production meshes.  A function (never module-level) so importing this file
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.

Target: TPU v5e, 16x16 = 256 chips per pod; 2 pods multi-pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated shardings).

    Since jax 0.7 ``make_mesh`` defaults to Explicit axes, under which the
    trainer's ``with_sharding_constraint`` and the serve cache's
    ``dynamic_update_slice`` are type errors; every mesh of this repo is
    built here."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return auto_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (~per-chip usable estimate)
HBM_BYTES = 16 * 1024 ** 3        # 16 GiB
