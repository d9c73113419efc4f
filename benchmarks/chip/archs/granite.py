"""Dense IBM Granite (``model_type`` granite): the granitemoe decoder with
no experts, which that module computes when ``num_local_experts`` is 0."""
from .granitemoe import (attention_flops, head_flops, is_gain,  # noqa: F401
                         logits, paged_attention_cost, param_shapes,
                         program_config, token_flops)
