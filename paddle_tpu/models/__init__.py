"""Model zoo: the flagship model families the reference's ecosystem trains
(PaddleNLP llm/ recipes — Llama-3, Qwen2/Qwen2-MoE; PaddleMIX — DiT), built
natively on paddle_tpu layers.

The reference keeps models out-of-tree (PaddleNLP/PaddleMIX); we ship them
in-tree because BASELINE.json's north-star configs are model-level
(Llama-3-8B pretrain, Qwen2-MoE, DiT) and the parallel plans in
paddle_tpu.parallel are keyed to these architectures.
"""
from paddle_tpu.models.llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, RMSNorm,
    llama3_8b_config, tiny_llama_config,
)
from paddle_tpu.models.qwen2_moe import (  # noqa: F401
    Qwen2MoeConfig, Qwen2MoeForCausalLM, tiny_qwen2_moe_config,
)
from paddle_tpu.models.bert import (  # noqa: F401
    BertConfig, BertModel, BertForSequenceClassification, BertForMaskedLM,
    bert_base_config, tiny_bert_config,
)
from paddle_tpu.models.gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, gpt2_small_config, tiny_gpt_config,
)
from paddle_tpu.models.dit import (  # noqa: F401
    DiTConfig, DiT, dit_xl_2_config, tiny_dit_config,
)
from paddle_tpu.models.generation import (  # noqa: F401
    generate, generate_speculative, generate_stream, init_kv_cache,
    process_logits,
)
from paddle_tpu.models.sparse_attn_moe import (  # noqa: F401
    SparseAttnMoeConfig, SparseAttnMoeForCausalLM, SparseAttnMoeModel,
    tiny_sparse_attn_moe_config,
)
from paddle_tpu.models.window_attn_moe import (  # noqa: F401
    WindowAttnMoeConfig, WindowAttnMoeForCausalLM, WindowAttnMoeModel,
    tiny_window_attn_moe_config,
)
from paddle_tpu.models.block_diffusion_moe import (  # noqa: F401
    BlockDiffusionMoeConfig, BlockDiffusionMoeForCausalLM,
    BlockDiffusionMoeModel, tiny_block_diffusion_moe_config,
)
from paddle_tpu.models.latent_attn_moe import (  # noqa: F401
    LatentAttnMoeConfig, LatentAttnMoeForCausalLM, LatentAttnMoeModel,
    tiny_latent_attn_moe_config,
)
from paddle_tpu.models.ssm_hybrid import (  # noqa: F401
    SsmHybridConfig, SsmHybridForCausalLM, SsmHybridModel,
    tiny_ssm_hybrid_config,
)
