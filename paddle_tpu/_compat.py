"""Runtime capability probes: questions asked of the device, never of
the JAX version (the repo targets the one installed JAX)."""
import jax

__all__ = ["host_memory_kind"]


def host_memory_kind(device=None):
    """Host-side memory kind for offload placement: ``pinned_host``
    where the device exposes it (TPU/GPU, newer CPU runtimes), else
    ``unpinned_host``. A device that cannot list its memories raises —
    a guessed kind would fail later, far from the cause."""
    dev = device if device is not None else jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    return "pinned_host" if "pinned_host" in kinds else "unpinned_host"
