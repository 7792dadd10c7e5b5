from tpucfn.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    MetricLogger,
    StepTimer,
    Summary,
    device_memory_stats,
    register_device_gauges,
)
from tpucfn.obs.flight import (  # noqa: F401
    FlightRecorder,
    hbm_watermark,
    read_flight_dir,
    read_flight_file,
)
from tpucfn.obs.goodput import (  # noqa: F401
    GoodputLedger,
    goodput_report,
    merge_goodput,
    read_goodput_dir,
)
from tpucfn.obs.profiler import (  # noqa: F401
    ProfileCapture,
    ProfilerBusy,
    enable_compile_cache,
    profile_steps,
    start_profiler_server,
)
from tpucfn.obs.registry import (  # noqa: F401
    Histogram,
    MetricRegistry,
    default_registry,
    set_default_labels,
)
from tpucfn.obs.server import (  # noqa: F401
    ObsServer,
    obs_port_from_env,
    start_obs_server,
)
from tpucfn.obs.trace import (  # noqa: F401
    Tracer,
    read_trace_dir,
    read_trace_file,
)
