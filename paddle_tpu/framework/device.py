"""Device management mirroring paddle.device.

Reference: /root/reference/python/paddle/device/__init__.py exposes
set_device/get_device with "gpu:0"-style strings backed by Place objects.
Here devices are jax.Device handles; "tpu"/"cpu" strings select platform.
"""
import jax


class TPUPlace:
    """Paddle-style Place handle for a TPU chip (≈ CUDAPlace in reference)."""

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"TPUPlace({self.device_id})"

    def __eq__(self, other):
        return isinstance(other, TPUPlace) and other.device_id == self.device_id


class CPUPlace:
    def __repr__(self):
        return "CPUPlace()"

    def __eq__(self, other):
        return isinstance(other, CPUPlace)


# Aliases so code written against the CUDA reference maps over.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace
NPUPlace = TPUPlace
IPUPlace = TPUPlace
MLUPlace = TPUPlace


class CustomPlace:
    """Place for a custom device type (reference core.CustomPlace)."""

    def __init__(self, device_type="tpu", device_id=0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"


class CUDAPinnedPlace:
    """Host-pinned staging memory place. On TPU, host buffers handed to
    jax.device_put are already staged through pinned memory; this is an
    API-parity handle (reference fluid CUDAPinnedPlace)."""

    def __repr__(self):
        return "CUDAPinnedPlace()"

    def __eq__(self, other):
        return isinstance(other, CUDAPinnedPlace)

_current = [None]  # lazily resolved default device string


def _platform():
    return jax.default_backend()


def set_device(device: str):
    """Accepts "tpu", "tpu:0", "cpu". Returns the jax.Device selected;
    raises where JAX has no such platform (never hands back CPU devices
    for "tpu")."""
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name in ("gpu", "cuda", "xpu"):  # compat: reference device names
        name = "tpu"
    devs = jax.devices(name)
    if idx >= len(devs):
        raise ValueError(f"device index {idx} out of range for {name} ({len(devs)} present)")
    jax.config.update("jax_default_device", devs[idx])
    _current[0] = f"{name}:{idx}"
    return devs[idx]


def get_device() -> str:
    if _current[0] is None:
        plat = _platform()
        plat = "tpu" if plat not in ("cpu",) else plat
        _current[0] = f"{plat}:0"
    return _current[0]


def is_compiled_with_cuda() -> bool:  # reference API parity; always False on TPU build
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return len(jax.devices())
