"""L3 policy/value networks: Flax encoders + actor-critic heads."""
from .encoders import MLPEncoder, CNNEncoder, GNNEncoder
from .actor_critic import (ActorCritic, GNNActorCritic, make_policy,
                           mask_logits, NEG_INF)
from .hier import HierActorCritic
from .trunk import TRUNKS, LingConfig, TokenTrunk, TrunkConfig

__all__ = ["MLPEncoder", "CNNEncoder", "GNNEncoder", "ActorCritic",
           "GNNActorCritic", "make_policy", "mask_logits", "NEG_INF",
           "HierActorCritic", "TokenTrunk", "TrunkConfig", "LingConfig",
           "TRUNKS"]
