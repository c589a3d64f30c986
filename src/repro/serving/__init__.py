"""Serving runtime: prefill + compression + FairKV slot-layout decode over
the paged cache, plus the continuous-batching request scheduler
(DESIGN.md §7)."""
from repro.serving.engine import (  # noqa: F401
    ServeState,
    decode_step,
    first_weights,
    init_serve_state,
    prefill,
    reset_state_rows,
    slotify_params,
    splice_state,
)
from repro.serving.request import (  # noqa: F401
    Request,
    RequestState,
    latency_percentiles,
    poisson_arrivals,
    synthesize_requests,
)
from repro.serving.scheduler import (  # noqa: F401
    ReplanTrigger,
    RowFreelist,
    Scheduler,
    SchedulerConfig,
)
from repro.serving.speculation import SpeculationConfig  # noqa: F401
