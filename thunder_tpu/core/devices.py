"""Devices and meshes, TPU-first.

Reference parity: ``thunder/core/devices.py`` models single accelerator
devices (CPU/CUDA/META). On TPU the natural unit is a *mesh* of devices
(`jax.sharding.Mesh`) plus per-array `NamedSharding` specs; a single device is
the degenerate 1-element mesh. This module provides:

- ``Device`` — a light wrapper over platform + index ("tpu:0", "cpu:0",
  "meta"), used for trace metadata and tests.
- ``MeshSpec`` — a declarative mesh description (axis names + sizes) that can
  be realized against the available ``jax.devices()`` (or CPU-emulated
  devices) into a ``jax.sharding.Mesh``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from enum import Enum
from typing import Any

import numpy as np


class DeviceType(Enum):
    CPU = "cpu"
    TPU = "tpu"
    GPU = "gpu"
    META = "meta"


_KNOWN = {d.value: d for d in DeviceType}


class Device:
    __slots__ = ("devicetype", "index")

    def __init__(self, devicetype: "DeviceType | str", index: int | None = None):
        if isinstance(devicetype, str):
            devicetype, parsed_index = _parse(devicetype)
            index = parsed_index if index is None else index
        self.devicetype = devicetype
        self.index = 0 if index is None and devicetype is not DeviceType.META else index

    @property
    def type(self) -> str:
        return self.devicetype.value

    def __eq__(self, other):
        return isinstance(other, Device) and self.devicetype is other.devicetype and self.index == other.index

    def __hash__(self):
        return hash((self.devicetype, self.index))

    def __repr__(self):
        if self.devicetype is DeviceType.META:
            return 'Device("meta")'
        return f'Device("{self.devicetype.value}:{self.index}")'

    def __str__(self):
        if self.devicetype is DeviceType.META:
            return "meta"
        return f"{self.devicetype.value}:{self.index}"

    def to_jax(self):
        import jax

        return jax.devices(self.devicetype.value)[self.index or 0]


def _parse(s: str) -> tuple[DeviceType, int | None]:
    if ":" in s:
        t, _, i = s.partition(":")
        return _KNOWN[t], int(i)
    return _KNOWN[s], None


def to_device(x: Any) -> Device:
    if isinstance(x, Device):
        return x
    if isinstance(x, str):
        return Device(x)
    if x is None:
        return default_device()
    # jax.Device
    if hasattr(x, "platform"):
        return Device(_platform_type(x.platform), getattr(x, "id", 0))
    raise TypeError(f"cannot interpret {x!r} as a Device")


def _platform_type(platform: str) -> DeviceType:
    """A jax platform name -> DeviceType. An unknown platform is an error:
    guessing CPU or TPU from the name would run the wrong plan silently."""
    if platform not in _KNOWN:
        raise ValueError(f"unknown jax platform {platform!r}; "
                         f"known: {sorted(_KNOWN)}")
    return _KNOWN[platform]


def default_device() -> Device:
    import jax

    d = jax.devices()[0]
    return Device(_platform_type(d.platform), d.id)


@dataclass(frozen=True)
class ChipSpec:
    """Published figures of one accelerator chip. ``name`` is the short key
    the calibration store and the budget files use (``tpu-<name>``)."""

    name: str
    peak_bf16_flops: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: int
    vmem_bytes: int             # physical VMEM of one core
    scoped_vmem_default_bytes: int  # Mosaic's per-kernel limit when a
    #                                 pallas_call passes no vmem_limit_bytes
    source: str


# THE device table, keyed by ``jax.devices()[0].device_kind`` exactly as the
# chip prints it. Every peak, bandwidth and budget in the tree (cost model,
# MFU arithmetic, calibration platform key) reads from here; a kind that is
# not listed is an error, never a default.
CHIP_SPECS: dict[str, ChipSpec] = {
    "TPU v5 lite": ChipSpec(
        name="v5e", peak_bf16_flops=197e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16 * 1024 ** 3,
        # VMEM figures are the compiler's own (libtpu 0.0.34, AOT compile
        # for this chip): a kernel staging 16.01 MiB is refused "limit
        # 16.00M" by default and a 64 MiB scoped allocation is accepted
        # once vmem_limit_bytes asks for it
        vmem_bytes=128 * 1024 * 1024,
        scoped_vmem_default_bytes=16 * 1024 * 1024,
        source='Google Cloud documentation, "TPU v5e"'),
}


def chip_spec(device_kind: str | None = None) -> ChipSpec:
    """The table row for ``device_kind`` (default: the first attached
    device's). Raises for a kind the table does not list."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in CHIP_SPECS:
        raise RuntimeError(
            f"device_kind {device_kind!r} is not in thunder_tpu.core.devices."
            f"CHIP_SPECS (known: {sorted(CHIP_SPECS)}) — add its published "
            f"figures with their source before running on it")
    return CHIP_SPECS[device_kind]


cpu = Device(DeviceType.CPU, 0)
meta = Device(DeviceType.META)


@dataclass(frozen=True)
class MeshSpec:
    """Declarative device-mesh description.

    axes: mapping from axis name to size; e.g. {"dp": 4, "tp": 2}.
    Realize with .build() against real or emulated devices.

    Conventional axis names used by the distributed transforms:
      "dp"  data parallel        "fsdp" fully-sharded data parallel
      "tp"  tensor parallel      "sp"   sequence/context parallel
      "ep"  expert parallel      "pp"   pipeline parallel
    """

    axis_names: tuple[str, ...]
    axis_sizes: tuple[int, ...]

    @staticmethod
    def make(**axes: int) -> "MeshSpec":
        return MeshSpec(tuple(axes.keys()), tuple(axes.values()))

    @property
    def size(self) -> int:
        return int(np.prod(self.axis_sizes)) if self.axis_sizes else 1

    def build(self, devices=None):
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        n = self.size
        if len(devices) < n:
            raise RuntimeError(f"mesh {self} needs {n} devices, have {len(devices)}")
        arr = np.array(devices[:n]).reshape(self.axis_sizes)
        return Mesh(arr, self.axis_names)

    def __repr__(self):
        inner = ", ".join(f"{n}={s}" for n, s in zip(self.axis_names, self.axis_sizes))
        return f"MeshSpec({inner})"
