#!/usr/bin/env python
"""ops.yaml name-resolution audit (DESIGN_DECISIONS.md §ops-audit).

Probes every `- op:` name in the reference's ops.yaml against the public
namespaces plus the _C_ops kernel surface. Prints the resolution ratio and
any unresolved names (expected: exactly the 11 recorded scope-outs).
"""

import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the reference tree is mounted beside the checkout where it exists at all;
# another location is passed as the first argument
OPS_YAML = os.path.join(os.path.dirname(_REPO), "reference", "paddle", "phi",
                        "ops", "yaml", "ops.yaml")

SCOPE_OUTS = {
    "batch_fc", "cvm", "match_matrix_tensor", "pyramid_hash",
    "rank_attention", "shuffle_batch", "tdm_child", "tdm_sampler",
    "dgc", "dgc_clip_by_norm", "dgc_momentum",
}


def main():
    ops_yaml = sys.argv[1] if len(sys.argv) > 1 else OPS_YAML
    names = []
    for line in open(ops_yaml):
        m = re.match(r"- op\s*:\s*(\w+)", line)
        if m:
            names.append(m.group(1))

    import paddle_tpu as paddle
    import paddle_tpu._C_ops as C
    import paddle_tpu.incubate.nn.functional as IF
    import paddle_tpu.nn.functional as F

    namespaces = [
        paddle, paddle.Tensor, F, C, IF, paddle.linalg, paddle.fft,
        paddle.signal, paddle.sparse, paddle.incubate, paddle.geometric,
        paddle.vision, paddle.vision.ops, paddle.nn, paddle.nn.quant,
        paddle.nn.utils, paddle.distributed, paddle.metric, paddle.text,
        paddle.static, paddle.amp, paddle.distribution,
    ]

    def resolves(n):
        cands = [n, n[:-1]] if n.endswith("_") else [n]
        return any(hasattr(ns, c) for c in cands for ns in namespaces)

    unresolved = [n for n in names if not resolves(n)]
    pct = 100.0 * (1 - len(unresolved) / len(names))
    print(f"ops.yaml names: {len(names)}  unresolved: {len(unresolved)}  "
          f"resolution: {pct:.1f}%")
    unexpected = [n for n in unresolved if n not in SCOPE_OUTS]
    for n in unresolved:
        tag = "" if n in SCOPE_OUTS else "  <-- NOT scope-recorded"
        print(f"  {n}{tag}")
    return 1 if unexpected else 0


if __name__ == "__main__":
    sys.exit(main())
