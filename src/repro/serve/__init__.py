"""Serving runtime: slot-paged persistent KV/SSM cache, bounded
request schedulers (FIFO and SLO-aware priority/preemption), in-jit
sampling, and the continuous-batching engine (plus the legacy
static-batch engine and its step factories)."""

from repro.serve.cache import SlotCache  # noqa: F401
from repro.serve.engine import (DecodeEngine, ServeEngine,  # noqa: F401
                                make_prefill_step, make_serve_step)
from repro.serve.prefix import PrefixPool, RadixIndex  # noqa: F401
from repro.serve.report import (SCENARIO_LIBRARY,  # noqa: F401
                                ServeScenario, TrafficItem,
                                bursty_tier_traffic, diurnal_tier_traffic,
                                heavy_tail_tier_traffic,
                                mixed_length_traffic, run_scenario,
                                scenario_waves, shared_prefix_traffic,
                                steady_tier_traffic, write_serve_report)
from repro.serve.sampling import (SamplerConfig, parse_sampler,  # noqa: F401
                                  sample)
from repro.serve.scheduler import (FinishedRequest,  # noqa: F401
                                   PriorityScheduler, QueueFull, Request,
                                   RequestScheduler, TierSLO)
