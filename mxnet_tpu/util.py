"""Misc utilities (parity shims for python/mxnet/util.py)."""


def is_np_array():
    return False


def is_np_shape():
    return False


def makedirs(d):
    import os

    os.makedirs(d, exist_ok=True)


def get_gpu_count():
    from .context import num_tpus

    return num_tpus()


def shard_map(f, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map`` with ``check_vma`` passed only when set — the
    one spelling every shard_map in this codebase goes through."""
    import jax

    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
