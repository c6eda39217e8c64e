"""Byte and operation counts, against counts made by hand."""

import json
import os

from helpers import ROOT  # noqa: F401  (puts src on the path)

from chipbench import costs


def test_kernel_bytes_by_hand():
    n = 768 * 768
    assert costs.kernel_bytes("snapshot_fused", n) == n * (4 + 4 + 1)
    assert costs.kernel_bytes("chain_apply", n, hops=5) == n * (4 + 5 + 4)
    assert costs.kernel_bytes("chain_apply", n, hops=2, q_itemsize=4) == \
        n * (4 + 8 + 4)
    assert costs.kernel_bytes("dequant_apply", n, q_itemsize=4) == n * 12
    assert costs.kernel_bytes("dequant_apply", n, itemsize=2, q_itemsize=1,
                              out_itemsize=2) == n * 5
    assert costs.kernel_bytes("fingerprint", n) == n * 4
    assert costs.kernel_bytes("snapshot_fused", n, itemsize=2) == n * 5


def _model(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name)) as f:
        return json.load(f)["model"]


def test_bert_base_flops_per_token_by_hand():
    m = _model("bert-base.json")
    per_layer = 4 * 768 * 768 + 2 * 768 * 3072          # attention + MLP
    n = 12 * per_layer + 768 * 30522                    # + output head
    assert costs.matmul_params(m) == n == 108_375_552
    attn = 12 * 12 * 512 * 768
    assert costs.train_flops_per_token(m, 512) == 6 * n + attn
    # about 0.71 GFLOP per token
    assert 0.70e9 < costs.train_flops_per_token(m, 512) < 0.72e9


def test_yi_matmul_params_by_hand():
    m = _model("yi-6b.l2.json")
    per_layer = (4096 * 4096 * 2 + 4096 * 512 * 2       # q, o, k, v
                 + 3 * 4096 * 11008)                    # gate, in, out
    assert costs.matmul_params(m) == 2 * per_layer + 4096 * 64000


def test_commit_and_checkout_bytes():
    leaves = [("a", (4, 4), "float32"), ("b", (8,), "bfloat16")]
    assert costs.commit_bytes(leaves) == 16 * 9 + 8 * 5
    assert costs.checkout_bytes(leaves, 3) == 16 * (8 + 3) + 8 * (4 + 3)
