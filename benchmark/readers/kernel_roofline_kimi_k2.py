"""Roofline share, in %, of one of the `kimi_k2` family's kernels, from the
device trace: the least time the chip could take for what the kernel's calls
require over the summed device time of the calls the trace shows under the
kernel's NAME (`%<kernel>.N`, a `tpu_custom_call`). What is required comes
from `costs_kimi_k2` and from what the program's own spans say of the traced
ticks (`engine.step/decode_dispatch`: `latent_tokens`;
`engine.step/admit/prefill`: `prompt_len`):

`decode_latent`: per traced tick the LARGER of the bytes of the decoded rows'
contexts (576 values a token and layer, whatever padding the stored row has)
over the bandwidth and their absorbed-form FLOP (2 x heads x (576 + 512) a
token and layer) over the bf16 peak; the ticks summed.
`flash_fwd` (bf16 peak): the admitted prompts' causal pairs in the expanded
form, 2 x heads x (192 + 128) FLOP a pair, every layer. The kernel runs over
the prompt's bucket, so a prompt that fills half its bucket reads a quarter.
The mix admits about one prompt a second, in clumps (an admission waits for
pages), so a deal may leave the traced 5 s without one: where the traced
ticks' spans were read and none of them admitted a prompt, the share reads 0
(none of the kernel's work was required and none was done;
`prefill_flash_share.k2r` reads 0 beside it), and the line still has it.
`grouped_gemm`: per call the LARGER of the bytes of the held experts' stacked
matrix it multiplies (told from the call's own `bf16[held, K, N]` operand)
over the bandwidth and the FLOP of the rows these experts are expected to be
sent of the call's pairs (its `bf16[M, K]` operand's M x held / published x K
x N x 2) over the peak. With 12 of 384 experts held a decode tick's call
finds about 24 rows, so some held experts get NO row and the kernel does not
read their matrices: for a call whose expected rows fit one row tile of 128
the bytes are the matrix's times the share of the held experts that got a
row, which the program counts: there every group lies in the first row tile,
so the visited tiles of the histogram `moe_rows_tiled` (one observation a
decode tick, all expert layers) are the experts hit. A call of more rows (a
prefill pass) reaches every held expert: the whole matrix.

Nothing to read (no trace, a program without the spans or the kernel, no
call of `decode_latent` or `grouped_gemm` inside the traced seconds): None."""

import re

from paddle_tpu.observability import metrics

from benchmark import costs_kimi_k2
from benchmark.readers.kernel_roofline_afmoe import _attrs
from benchmark.readers.kernel_roofline_hybrid import _calls


ROW_TILE = 128   # `held_moe._row_tile` for a pass of few rows an expert


def _experts_hit_share(config):
    """The mean share of the held experts that a decode tick's call finds a
    row for, from the program's histogram of visited row tiles; None where
    the program has no such histogram or it never counted."""
    tiled = metrics.default_registry().get("moe_rows_tiled")
    if tiled is None or not tiled.count():
        return None
    groups = costs_kimi_k2.expert_layers(config) * config["n_routed_experts"]
    return min(1.0, tiled.sum() / tiled.count() / ROW_TILE / groups)


def read(run, obs, kernel):
    trace = run.trace
    if trace is None or len(trace.devices) != 1:
        return None
    calls = _calls(trace, kernel)
    kernel_s = sum(d for _, d in calls)
    if kernel == "flash_fwd":
        prompts = _attrs(run, "engine.step/admit/prefill", "prompt_len")
        if prompts == []:
            return 0.0   # the traced ticks admitted no prompt
    if kernel_s <= 0:
        return None
    config, peaks = run.config, run.peaks()
    bandwidth, peak = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    if kernel == "decode_latent":
        ticks = _attrs(run, "engine.step/decode_dispatch", "latent_tokens")
        if not ticks:
            return None
        least_s = sum(
            max(costs_kimi_k2.decode_latent_bytes(config, t) / bandwidth,
                costs_kimi_k2.decode_latent_flops(config, t) / peak)
            for t in ticks)
    elif kernel == "flash_fwd":
        if prompts is None:
            return None
        least_s = costs_kimi_k2.mla_prefill_flops(config, prompts) / peak
    elif kernel == "grouped_gemm":
        by_elements = {b // 2: b for b in
                       costs_kimi_k2.grouped_gemm_weight_bytes(config)}
        share = (config["n_routed_experts"]
                 / config["published"]["n_routed_experts"])
        stacked = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")
        rows_of = re.compile(r"custom-call\(.*?bf16\[(\d+),(\d+)\]")
        hit = _experts_hit_share(config)
        if hit is None:
            return None
        least_s = 0.0
        for name, _ in calls:
            known = [(int(k) * int(n), by_elements[int(e) * int(k) * int(n)])
                     for e, k, n in stacked.findall(name)
                     if int(e) * int(k) * int(n) in by_elements]
            pairs = rows_of.search(name)
            if not known or pairs is None:
                return None   # a call that multiplies something else
            k_times_n, weight_bytes = known[0]
            rows = int(pairs.group(1)) * share
            if rows <= ROW_TILE:
                weight_bytes *= hit
            flops = rows * k_times_n * 2
            least_s += max(weight_bytes / bandwidth, flops / peak)
    else:
        raise ValueError(f"no count for kernel {kernel!r}")
    return 100.0 * least_s / kernel_s if least_s else None
