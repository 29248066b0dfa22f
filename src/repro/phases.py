"""Names of the train step's phases. Each wraps its work in
``jax.named_scope(<name>)``, so the name is a component of the
``op_name`` metadata of every HLO instruction the phase emits: forward,
remat recompute (``rematted_computation``) and backward (``transpose(``)
alike."""

EMBED = "embed"  # token lookup
ATTENTION = "attention"  # a layer's attention sub-block, with its pre-norm
ATTENTION_CORE = "attention_core"  # score, softmax and PV, in ATTENTION
MLP = "mlp"  # a layer's MLP or MoE sub-block, with its pre-norm
HEAD_LOSS = "head_loss"  # final norm, logits or chunked unembed, loss
GRAD_PACK = "grad_pack"  # gradients cast and packed into the f32 buffer
LARS_NORMS = "lars_norms"  # per-slice weight and update norms
TRUST_RATIO = "trust_ratio"  # trust ratios and the adaptation mask
LARS_APPLY = "lars_apply"  # momentum, decay and weight update
PARAM_UNPACK = "param_unpack"  # new master weights to storage-dtype params
ROUTER = "router"  # an MoE layer's router: logits, top-k gates, balance loss
MOE_DISPATCH = "moe_dispatch"  # sort of the held slots, gather, combine
MOE_EXPERTS = "moe_experts"  # the held experts' grouped matmuls
