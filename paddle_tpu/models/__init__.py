from . import gpt  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from . import llama  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel  # noqa: F401
from . import laguna  # noqa: F401
from .laguna import LagunaConfig, LagunaForCausalLM, LagunaModel  # noqa: F401
from . import glm_moe_lite  # noqa: F401
from .glm_moe_lite import (  # noqa: F401
    GlmMoeLiteConfig,
    GlmMoeLiteForCausalLM,
    GlmMoeLiteModel,
)
from . import xing  # noqa: F401
from .xing import Xing4Config, Xing4ForCausalLM, Xing4Model  # noqa: F401
from . import glm5_flash  # noqa: F401
from .glm5_flash import (  # noqa: F401
    Glm5FlashConfig,
    Glm5FlashForCausalLM,
    Glm5FlashModel,
)
from . import bert  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
)
