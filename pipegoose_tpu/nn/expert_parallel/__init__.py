from pipegoose_tpu.nn.expert_parallel.expert_parallel import ExpertParallel
from pipegoose_tpu.nn.expert_parallel.experts import (
    expert_mlp,
    grouped_experts,
    init_experts,
    moe_layer,
    swiglu_grouped,
)
from pipegoose_tpu.nn.expert_parallel.loss import ExpertLoss
from pipegoose_tpu.nn.expert_parallel.routers import (
    RouterOutput,
    SigmoidTopKRouter,
    SoftmaxTopKRouter,
    SwitchNoisePolicy,
    Top1Router,
    Top2Router,
    TopKRouter,
    TopKRouting,
)

__all__ = [
    "ExpertParallel",
    "expert_mlp",
    "grouped_experts",
    "init_experts",
    "moe_layer",
    "swiglu_grouped",
    "ExpertLoss",
    "RouterOutput",
    "SigmoidTopKRouter",
    "SoftmaxTopKRouter",
    "SwitchNoisePolicy",
    "Top1Router",
    "Top2Router",
    "TopKRouter",
    "TopKRouting",
]
