"""Mixture-of-Experts layer with per-layer (LExI) top-k.

Structured as a ``Router -> Dispatch -> Compute -> Combine`` pipeline:

  ``router.py``    expert scoring / top-k / capacity sizing
  ``dispatch.py``  token movement: capacity buffers + sort-based dropless
  ``compute.py``   expert SwiGLU over each layout (jnp or Pallas kernel)
  ``dense.py``     GShard capacity-buffer impl (reference / small scale)
  ``gmm.py``       sort-based dropless impl (production prefill path)
  ``decode.py``    fused routed-expert impl (production decode path)
  ``ep.py``        shard_map expert parallelism (a2a train, psum decode)
  ``registry.py``  impl registry + the public ``moe()`` entry

The router follows each model family: softmax or sigmoid scoring, optional
top-k renormalization, shared (always-on) experts.  All impls are
numerically equivalent up to capacity drops (``gmm`` is exactly dropless)
and are pinned against each other in tests.
"""

from repro.models.moe.compute import (  # noqa: F401
    add_shared,
    expert_ffn,
    grouped_ffn,
    grouped_ffn_quant,
    quant_leaves,
    routed_ffn,
    routed_ffn_quant,
)
from repro.models.moe.decode import moe_decode  # noqa: F401
from repro.models.moe.dense import moe_dense  # noqa: F401
from repro.models.moe.dispatch import (  # noqa: F401
    SortPlan,
    _gather_combine,
    _scatter,
    _slot_positions,
    default_block_m,
    make_sort_plan,
    sort_combine,
    sort_dispatch,
)
from repro.models.moe.ep import (  # noqa: F401
    _ep_param_specs,
    moe_ep_a2a,
    moe_ep_a2a_local,
    moe_ep_psum,
    moe_ep_psum_local,
)
from repro.models.moe.gmm import moe_gmm  # noqa: F401
from repro.models.moe.params import (  # noqa: F401
    QUANT_DTYPES,
    dequantize_experts,
    init_moe,
    quantize_expert_params,
    quantize_experts,
    quantize_moe_layer,
    unpack_int4,
)
from repro.models.moe.registry import (  # noqa: F401
    DECODE_TOKEN_THRESHOLD,
    available_impls,
    moe,
    register_impl,
    resolve_impl,
)
from repro.models.moe.router import (  # noqa: F401
    capacity,
    route,
    route_lookahead,
    routed_experts,
)

# back-compat alias for callers of the pre-package private helper
_add_shared = add_shared
