"""Which device operations belong to which kernel or program, and the
roofline and share arithmetic over them."""
from __future__ import annotations

import re

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


def relabel_ops(ops, rows: int) -> list:
    """The relabel's device operations: every run of the programs that
    hold an op shaped by the relabel subset's `rows` and ran fewest
    times.  The relabel program (one `jit__lambda`, like the local and
    distillation steps, whose op events carry no scope or source) and
    the gathers that draw its subset run once a round; the gathers that
    draw each distillation batch from the relabelled buffer, also
    shaped by `rows`, run once a step.  Without module runs in the
    trace, none."""
    dim = re.compile(rf"[\[,]{rows}[\],]")
    runs: dict = {}
    for o in ops:
        if o.run >= 0 and dim.search(o.desc or o.name):
            runs.setdefault((o.module, o.program), set()).add(o.run)
    if not runs:
        return []
    fewest = min(len(r) for r in runs.values())
    keep = {(m, p, r) for (m, p), rs in runs.items() if len(rs) <= 2 * fewest
            for r in rs}
    return [o for o in ops if (o.module, o.program, o.run) in keep]


def relabel_share(run):
    ops = relabel_ops(run.trace["ops"], run.res["ec"]["relabel_rows"])
    if not ops:
        return None
    return 100.0 * sum(o.dur for o in ops) * 1e-9 / run.trace["busy_s"]
