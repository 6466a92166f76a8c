"""Model zoo: TPU-first pure-functional models (pytree params + jit-able
apply fns, logical-axis sharding annotations)."""

from .transformer import (  # noqa: F401
    TransformerConfig,
    init_params,
    init_paged_kv_cache,
    param_specs,
    make_paged_decoder,
    pack_decode_inputs,
    pack_prefill_inputs,
    make_forward,
    make_loss_fn,
    CONFIGS,
    KV_CACHE_AXES,
)
from .kv_paging import (  # noqa: F401
    BlockAllocator,
    InsufficientBlocksError,
    PagedDecodeEngine,
    PrefixCache,
)
from .speculative import (  # noqa: F401
    NGramDrafter,
    ReplayDrafter,
    resolve_drafter,
)
from . import hub  # noqa: F401  — real checkpoints + tokenizers (model hub)
