"""The names the program puts on its own work, for profiles.

Device scopes (``jax.named_scope``) are HLO metadata: every op lowered
inside one carries the scope in its name stack, so a profile of the
compiled program keys each op back to the scope, at no cost on the
device.  Host spans (``jax.profiler.TraceAnnotation``) land on the host
plane of the same trace, on the clock the profiler aligns with the
device's; without a profiler running one costs about a microsecond.

Every name starts with ``PREFIX``.  DESIGN.md §16 says what each tells
an operator; ``bench/scopes.py`` reads them from a traced window, and
``launch/train.py --profile-dir`` writes traces that hold them.
"""

PREFIX = "tamuna."

# device scopes
LOCAL_STEP = "tamuna.local_step"  # the local-step scan of a round chunk
COMM_STEP = "tamuna.comm_step"  # the comm step (UpCom, h-update, DownCom)
UPLINK = "tamuna.uplink"  # the aggregation kernels inside it
COHORT_GATHER = "tamuna.cohort_gather"  # cohort rows -> compact state
COHORT_SCATTER = "tamuna.cohort_scatter"  # compact state -> cohort rows
TRACES = "tamuna.traces"  # the per-round metric trace writes

# host spans
INIT_CARRY = "tamuna.init_carry"  # a fresh carry: keys and zero traces
ROUND = "tamuna.round"  # one round: its L draw and program calls
DRAIN = "tamuna.drain"  # the device_get of the traces, the host sync
FLUSH = "tamuna.flush"  # metric rows, the logger, the trace reset
CHECKPOINT = "tamuna.checkpoint"  # a checkpoint save
COMPILE = "tamuna.compile."  # + bucket: a round program's first call
