from localai_tpu.ops.pallas.flash_attention import (  # noqa: F401
    flash_prefill,
    ragged_decode,
    ragged_decode_q8,
)
from localai_tpu.ops.pallas.paged_scatter import (  # noqa: F401
    paged_scatter_append,
    paged_scatter_append_q8,
    paged_scatter_append_q8_sharded,
    paged_scatter_append_sharded,
)
