"""Device meshes and per-device peak rates.

``make_production_mesh`` is a FUNCTION (never a module constant) so that
importing this module does not touch jax device state — smoke tests must
keep seeing 1 CPU device; only dryrun.py forces 512 host devices.
"""
from __future__ import annotations

import dataclasses

import jax

SINGLE_POD = (16, 16)               # 256 chips
MULTI_POD = (2, 16, 16)             # 2 pods x 256 chips
# the chip the production meshes are sized for (jax's ``device_kind``);
# the dry-run compiles for it on placeholder host devices
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks used by the roofline (launch/analysis)."""
    flops_bf16: float               # FLOP/s
    hbm_bw: float                   # bytes/s
    ici_bw: float                   # bytes/s per link
    source: str


# keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of one chip of ``device_kind``; an unknown device is an
    error, never a default (its roofline would be fiction)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") \
            from None


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def _check_visible(n: int) -> None:
    visible = len(jax.devices())
    if n > visible:
        raise ValueError(f"mesh needs {n} devices but only {visible} are "
                         f"visible")


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the local devices; asking for more
    devices than are visible is an error."""
    _check_visible(data * model)
    return _make_mesh((data, model), ("data", "model"))


def make_peer_mesh(devices: int = 0):
    """1-axis validator mesh: the Gauntlet's round entry points shard
    their *scored-peer* dimension over this axis (sharding.PEER_AXIS).

    ``devices`` = 0 takes every visible device; more than are visible
    is an error. On CPU CI the count is forced up front with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (device count
    is locked at first jax init, so the env var must be set before any
    jax call — see tests/test_steps_distributed.py for the subprocess
    pattern)."""
    from repro.sharding import PEER_AXIS
    n = int(devices) or len(jax.devices())
    _check_visible(n)
    return _make_mesh((n,), (PEER_AXIS,))
