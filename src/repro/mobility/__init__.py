from repro.mobility.patterns import (  # noqa: F401
    commuter_trace, duty_cycle_mask, event_crowd_trace, flash_churn_mask,
    markov_churn_mask, multi_area_trace, shift_worker_trace)
from repro.mobility.random_walk import (  # noqa: F401
    MobilityConfig, init_mobility, mobility_step, simulate_trajectories, space_of)
from repro.mobility.streaming import (  # noqa: F401
    CommuterStream, CompactColocation, commuter_stream, compact_colocation,
    dense_colocation, materialize_generator, reorder_generator_arrays)
from repro.mobility.trace import (  # noqa: F401
    area_over_time, dwell_exchange_flags, synth_foursquare_trace,
    trace_to_colocation, trace_to_colocation_loop)
