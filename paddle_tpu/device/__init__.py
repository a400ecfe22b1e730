"""paddle_tpu.device (parity: python/paddle/device/)."""
from ..framework.place import (CPUPlace, CUDAPlace, Place, TPUPlace,  # noqa: F401
                               XPUPlace, device_count, get_device,
                               is_compiled_with_cuda, is_compiled_with_tpu,
                               is_compiled_with_xpu, set_device)

__all__ = ["set_device", "get_device", "device_count", "is_compiled_with_cuda",
           "is_compiled_with_xpu", "is_compiled_with_tpu", "TPUPlace",
           "CPUPlace", "CUDAPlace", "XPUPlace", "Place", "cuda", "synchronize"]


def synchronize(device=None):
    """Block until all queued device work completes (reference:
    platform device_context Wait). JAX: handled per-array; this flushes by
    touching a trivial computation."""
    import jax
    jax.effects_barrier()


class cuda:
    """Compat namespace: paddle.device.cuda.* maps to the single accelerator."""

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def memory_allocated(device=None):
        from ..framework.monitor import memory_allocated
        return memory_allocated(device)

    @staticmethod
    def max_memory_allocated(device=None):
        from ..framework.monitor import max_memory_allocated
        return max_memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        from ..framework.monitor import device_memory_stats
        s = device_memory_stats(device)
        return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))
