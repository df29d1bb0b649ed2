"""Model FLOP utilization of the window: the operations of the work
completed in it (each prompt's prefill at its true length, each decoded
token with attention over its true context, not the padded ``max_len``)
over the window's length times the chip's bf16 peak."""
from bench import counts


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    if not peak or run.window_s <= 0:
        return None
    flops = 0
    for r in run.requests:
        for k, t in enumerate(r.stamps):
            if not run.in_window(t):
                continue
            if k == 0:
                flops += counts.prefill_flops(run.sizes, r.prompt_len,
                                              r.n_patches)
            else:
                flops += counts.decode_flops(run.sizes, r.prompt_len + k)
    if not flops:
        return None
    return 100.0 * flops / (run.window_s * peak)
