from .bert import (  # noqa: F401
    BertConfig, BertForPretraining, BertModel, BertPretrainingCriterion,
    bert_base, bert_large, bert_tiny, ernie_base,
)
from .kimi_linear import (  # noqa: F401
    KimiLinearConfig, KimiLinearForCausalLM, kimi_linear_tiny,
)
from .lfm2_moe import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeForCausalLM, lfm2_moe_tiny,
)
from .minimax_m3 import (  # noqa: F401
    MiniMaxM3Config, MiniMaxM3ForCausalLM, minimax_m3_tiny,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel, RMSNorm,
    llama_tiny, llama_7b, llama_13b,
)
from .pangu_ultra_moe import (  # noqa: F401
    PanguUltraMoEConfig, PanguUltraMoEForCausalLM, pangu_ultra_moe_tiny,
)
from .transformer import (  # noqa: F401
    CrossEntropyCriterion, TransformerConfig, TransformerModel,
    greedy_translate, transformer_base, transformer_big, transformer_tiny,
)

__all__ = [
    "BertConfig", "BertForPretraining", "BertModel",
    "BertPretrainingCriterion", "bert_base", "bert_large", "bert_tiny",
    "ernie_base",
    "KimiLinearConfig", "KimiLinearForCausalLM", "kimi_linear_tiny",
    "Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_moe_tiny",
    "LlamaConfig", "LlamaForCausalLM", "LlamaModel", "RMSNorm",
    "llama_tiny", "llama_7b", "llama_13b",
    "MiniMaxM3Config", "MiniMaxM3ForCausalLM", "minimax_m3_tiny",
    "PanguUltraMoEConfig", "PanguUltraMoEForCausalLM",
    "pangu_ultra_moe_tiny",
    "CrossEntropyCriterion", "TransformerConfig", "TransformerModel",
    "greedy_translate", "transformer_base", "transformer_big",
    "transformer_tiny",
]
