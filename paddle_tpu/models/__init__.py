"""Flagship model families (GPT/ERNIE-class LLMs, BERT)."""

from .gpt import (GPTAttention, GPTBlock, GPTConfig, GPTForCausalLM, GPTMLP,
                  GPTModel, PagedKVCache, StaticKVCache, ernie_10b,
                  gpt_125m, gpt_1p3b, gpt_350m, gpt_tiny,
                  paged_cache_create, paged_kv_append)
from .cache_layout import (LatentCache, LayerCache, StateCache,
                           UnsupportedCacheLayout, ring_pages)
from .glm4_moe_lite import (Glm4MoeLiteConfig, Glm4MoeLiteForCausalLM,
                            glm4_7_flash, glm4_moe_lite_tiny)
from .smallthinker import (SmallThinkerConfig, SmallThinkerForCausalLM,
                           smallthinker_21b_a3b, smallthinker_tiny)
from .solar_open2 import (SolarOpen2Config, SolarOpen2ForCausalLM,
                          solar_open2_250b, solar_open2_tiny)
