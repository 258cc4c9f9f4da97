"""Which device operations belong to which kernel or program, and the
roofline and share arithmetic over them."""
from __future__ import annotations

from harness import flops, xtrace


def is_paged_attn(op: xtrace.Op) -> bool:
    """The Pallas paged-attention kernel.  Alone it compiles to a
    `tpu_custom_call` named `paged_attention.<n>`; inlined in the decode
    step it is renamed (`closed_call.<n>`, in the step compiled for a
    v5e), and the Pallas call there is the only `tpu_custom_call`, so
    that target in the op's HLO text marks it inside `_step_impl`.  The
    pool transposes before the call are fusions, not kernel time."""
    return op.name.startswith("paged_attention") or (
        "step_impl" in op.module and "tpu_custom_call" in op.desc)


def paged_attn_roofline(run):
    """% of the roofline: the least time the window's decode tokens need
    in the kernel over the kernel's device time (None without either)."""
    s = run.res["serve"]
    m = s["model"]
    t_kernel = xtrace.kernel_ns(run.trace["ops"], is_paged_attn) * 1e-9
    if not s["decode_ctx"] or t_kernel <= 0:
        return None
    fl = by = 0.0
    for ctx in s["decode_ctx"]:
        f, b = flops.paged_attn_cost(m, s["kv_dtype"], ctx, s["page_size"])
        fl, by = fl + f, by + b
    calls = m["n_layers"] * m["members"]
    t_min = max(fl * calls / run.peak["bf16_flops"],
                by * calls / run.peak["hbm_bytes_per_s"])
    return 100.0 * t_min / t_kernel
