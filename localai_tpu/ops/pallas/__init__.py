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
from localai_tpu.ops.pallas.ragged_attention import (  # noqa: F401
    QBLK,
    ragged_attention_xla,
    ragged_attention_xla_q8,
    ragged_paged_attention,
    ragged_paged_attention_q8,
    ragged_paged_attention_q8_sharded,
    ragged_paged_attention_sharded,
    ragged_scatter_append,
    ragged_scatter_append_q8,
    ragged_scatter_append_q8_sharded,
    ragged_scatter_append_sharded,
    ragged_scatter_xla,
    ragged_scatter_xla_q8,
)
