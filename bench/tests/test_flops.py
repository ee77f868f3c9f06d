"""bench/flops.py against hand counts at the configuration."""
from bench import common, flops
from bench.reference import dense_gqa


def test_internlm2_matmul_params():
    m = dense_gqa.dims_from_spec(common.config_spec("internlm2-20b-l1v8"))
    attn = 6144 * 8192 + 6144 * 6144          # wq wk wv, wo
    mlp = 3 * 6144 * 16384
    head = 6144 * 11568
    assert flops.dense_matmul_params(m) == attn + mlp + head == 461144064
    assert flops.dense_attention_flops(m, 10) == 4 * 48 * 128 * 10
    assert flops.causal_sum(3) == 1 + 2 + 3
    assert flops.causal_sum(2, 5) == 6 + 7
