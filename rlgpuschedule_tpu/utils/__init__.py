"""L6 auxiliary utilities: metrics logging, profiling/tracing."""
import time as _time

_t_import = _time.monotonic()
from .logging import MetricsLogger, TensorBoardWriter, ThroughputMeter
from .profiling import trace, debug_checks, SectionTimer

from .. import stamp as _stamp

# where a chip entry point pays for ``import jax`` (obs.startup)
_stamp("import", _t_import)

__all__ = ["MetricsLogger", "TensorBoardWriter", "ThroughputMeter",
           "trace", "debug_checks", "SectionTimer"]
