#!/usr/bin/env python3
"""Readings of the controls that every compared number must separate from
the program's, at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

Lineage cells: the reference's reconstruction computed one precision lower
(``reference/lineage.py``'s ``control``) against the reference, over the
cell's chain and the derivatives its window commits; the number is the count
of elements whose bits differ.

Training cells: the reference computed one precision below the one the
configuration states (``high``, three bfloat16 passes, for float32 at
``highest``; bfloat16 for other float32), and the reference with half of
every batch left out (the fault a data path can have), each against the
reference: the same loss, gradient and change gaps a run compares. A step
that returns its state unchanged reads 1 on the gradient and change gaps by
their definition and needs no run.

One JSON line per seed on standard output. The benchmark's own runs never
run this; it sets the upper end of each limit (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# the TPU runtime would log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench.harness import BENCH_DIR, Cell  # noqa: E402


def lineage_control(cell: Cell, seed: int) -> Dict[str, Any]:
    import numpy as np
    from chipbench import generate
    from chipbench.reference import lineage as ref
    mix, model = cell.mix, cell.config["model"]
    eps = mix.get("eps", 1e-4)
    specs = generate.leaf_specs(model)
    ft = mix["finetune"]

    def derive(p, tag):
        return generate.finetune(p, seed, tag, density=ft["density"],
                                 scale=ft["scale"],
                                 freeze_frac=ft["freeze_frac"])
    gens = [generate.base_weights(specs, seed)]
    for k in range(1, mix["chain_depth"] + 1):
        gens.append(derive(gens[-1], k))
    kids = [(d, derive(gens[d], 1 + i))
            for i, d in enumerate(mix["commit_parents"][:3])]
    bad = compared = 0
    for key, _, _ in specs:
        truth, fold = [np.asarray(gens[0][key])], [None]
        for k in range(1, len(gens)):
            child = np.asarray(gens[k][key])
            low = ref.control(truth[-1], fold[-1], child, eps)
            t, f = ref.truth(truth[-1], fold[-1], child, eps)
            bad += ref.mismatches(low, t)
            compared += t.size
            truth.append(t)
            fold.append(f)
        for d, kid in kids:
            child = np.asarray(kid[key])
            t, _ = ref.truth(truth[d], fold[d], child, eps)
            bad += ref.mismatches(ref.control(truth[d], fold[d], child, eps),
                                  t)
            compared += t.size
    return {"control_mismatched_elements": bad, "compared": compared}


def train_control(cell: Cell, seed: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from chipbench.drivers import train
    from repro.models.model import param_structs
    mix, model = cell.mix, cell.config["model"]
    template = param_structs(train.program_config(cell))
    params0, batches = train.reference_inputs(cell, seed, template)
    rows = int(mix["reference_rows"])
    run = lambda **kw: train.reference_model(cell).train(  # noqa: E731
        params0, batches, model, mix["optimizer"], rows=rows, **kw)
    ref = run()
    p0 = jax.device_get(params0)
    b1 = mix["optimizer"]["b1"]
    out: Dict[str, Any] = {}
    lower = ({"precision": "high"}
             if cell.config.get("matmul_precision") == "highest"
             else {"dtype": jnp.bfloat16})
    for name, kw in (("control", lower), ("half_batch", {"half_batch": True})):
        losses, grad, params3 = run(**kw)
        # the control stands in the program's place: its first gradient
        # reaches the comparison as the optimizer's first moment would
        mu1 = jax.tree_util.tree_map(lambda g: g * (1 - b1), grad)
        got = train.readings(losses, mu1, params3, ref, p0, b1)
        out.update({f"{name}.{k}": v for k, v in got.items()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = Cell(args.workload, None, BENCH_DIR)
    harness.enable_compile_cache()
    fn = lineage_control if cell.mix["kind"] == "lineage" else train_control
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name, "seed": seed,
                          **fn(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
