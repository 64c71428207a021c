from dist_dqn_tpu.agents.dqn import (  # noqa: F401
    LearnerState, make_learner, make_actor_step, make_optimizer)
from dist_dqn_tpu.agents.r2d2 import (  # noqa: F401
    make_r2d2_learner, make_recurrent_actor_step)
from dist_dqn_tpu.agents.agent import Agent, make_agent  # noqa: F401
