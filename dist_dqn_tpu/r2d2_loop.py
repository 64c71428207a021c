"""Debt D1c (ROADMAP.md): perf/tools/compile_rehearsal.py imports this name.
The recurrent program is train_loop.make_fused_train's, like every other;
the next `benchmark` PR points the tool there and deletes this file."""
from dist_dqn_tpu.train_loop import make_fused_train as make_r2d2_train  # noqa: F401
