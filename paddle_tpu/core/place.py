"""Device placement.

Parity with the reference's Place hierarchy (`paddle/phi/common/place.h`) and
`paddle.device.set_device` (`python/paddle/device/__init__.py`), mapped onto
jax devices. The TPU place is first-class; CPU is the host fallback.
"""
from __future__ import annotations

import jax


class Place:
    """Base place. Equality is by (kind, device_id)."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def jax_device(self):
        """The jax device this place names. A place whose platform or
        index the process does not have raises: handing back some other
        device would run the caller's work where it did not ask."""
        devs = [d for d in jax.devices() if d.platform == self.kind]
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"{self!r}: this process has {len(devs)} {self.kind} "
                f"device(s) (jax.devices() = {jax.devices()})")
        return devs[self.device_id]


def on_tpu_backend() -> bool:
    """True when the default jax backend is a TPU. The single shared
    predicate for TPU-only fast paths (Pallas kernels, rbg RNG). A
    backend that fails to initialise raises here — it is never read
    as "not a TPU"."""
    return jax.default_backend() == "tpu"


def holds_accelerator() -> bool:
    """True when this process has initialised a jax backend that is not
    the CPU — i.e. it owns the chip. A chip belongs to one process at a
    time, so a process for which this is True must not start a child
    that needs the chip: the child fails or hangs. The elastic
    supervisor checks this before it spawns a trainer. (jax has no
    public "is a backend initialised" query; asking `jax.devices()`
    would itself claim the chip.)"""
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() != "cpu"


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    kind = "tpu"


# paddle calls its accelerator place CUDAPlace; we keep an alias so ported
# user code keeps working, but it resolves to the TPU.
CUDAPlace = TPUPlace

_current_place: Place | None = None


def _default_place() -> Place:
    return TPUPlace(0) if on_tpu_backend() else CPUPlace(0)


def get_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def set_device(device) -> Place:
    """paddle.device.set_device('tpu:0' | 'cpu' | 'gpu:0') parity."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    s = str(device).lower()
    dev_id = 0
    if ":" in s:
        s, idx = s.split(":", 1)
        dev_id = int(idx)
    if s in ("tpu", "gpu", "cuda", "xpu", "npu"):
        _current_place = TPUPlace(dev_id)
    elif s == "cpu":
        _current_place = CPUPlace(dev_id)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _current_place


def get_device() -> str:
    p = get_place()
    return f"{p.kind}:{p.device_id}"


def is_compiled_with_cuda() -> bool:  # parity shim; we are TPU-native
    return False


def is_compiled_with_tpu() -> bool:
    return on_tpu_backend()
