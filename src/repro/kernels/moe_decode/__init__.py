from repro.kernels.moe_decode.ops import expert_mlp
from repro.kernels.moe_decode.ref import expert_mlp_reference
