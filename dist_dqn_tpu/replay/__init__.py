from dist_dqn_tpu.replay.device import (  # noqa: F401
    TimeRingState, gather_transitions, time_ring_init, time_ring_add,
    time_ring_sample, time_ring_can_sample)
from dist_dqn_tpu.replay.device_ring import (  # noqa: F401
    DeviceRing, make_device_ring)
from dist_dqn_tpu.replay.host import (  # noqa: F401
    PrioritizedHostReplay, SumTree, UniformHostReplay)
from dist_dqn_tpu.replay.prioritized_device import (  # noqa: F401
    PrioritizedRingState, prioritized_ring_add, prioritized_ring_init,
    prioritized_ring_sample, prioritized_ring_update)
from dist_dqn_tpu.replay.sequence_device import (  # noqa: F401
    SequenceRingState, sequence_ring_add, sequence_ring_can_sample,
    sequence_ring_init, sequence_ring_sample, sequence_ring_update)
