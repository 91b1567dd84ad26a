"""Kernels (merge): device milliseconds per pass of the segmented and final
top-k merge programs in the trace."""

# the merge programs' HLO modules: "jit__segmented_merge_topk_jnp(...)",
# "jit__merge_topk_jnp(...)"
MERGE_MODULES = ("jit__segmented_merge_topk_jnp(", "jit__merge_topk_jnp(")


def is_merge(op) -> bool:
    return op.module.startswith(MERGE_MODULES)


def read(r):
    if r.device is None or not r.passes:
        return None
    busy = r.device.op_seconds(is_merge)
    if busy <= 0:
        return None
    return 1e3 * busy / r.passes
