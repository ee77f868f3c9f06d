"""Required work at the configuration, reached as the GRPO kind reaches it:
through the module that the configuration's ``reference`` names."""
from bench import common, flops

SPEC = common.config_spec("internlm2-20b-l1v8")
REF = common.reference_module(SPEC["reference"])


def test_internlm2_matmul_params():
    m = REF.dims_from_spec(SPEC)
    attn = 6144 * 8192 + 6144 * 6144          # wq wk wv, wo
    mlp = 3 * 6144 * 16384
    head = 6144 * 11568
    assert flops.dense_matmul_params(m) == attn + mlp + head == 461144064
    assert flops.dense_attention_flops(m, 10) == 4 * 48 * 128 * 10
    assert flops.causal_sum(3) == 1 + 2 + 3
    assert flops.causal_sum(2, 5) == 6 + 7


def test_internlm2_iteration_work():
    """One iteration of ``grpo_async_g8``: 4 groups of a 512-token prompt
    and 8 responses of 384 tokens, the integers the kind's own count gave
    before it moved into the reference module."""
    t = common.traffic_spec("grpo_async_g8")
    group = (t["prompt_len"], [t["max_response_len"]] * t["group_size"])
    sizes = {5: [group] * t["prompts_per_iteration"], 6: [group]}
    work = REF.window_work(REF.dims_from_spec(SPEC), sizes, {5}, t, {})
    assert work == {"train_flops": 53790338187264,
                    "rollout_flops": 13447584546816}
