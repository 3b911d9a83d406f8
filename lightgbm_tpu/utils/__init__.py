from .log import Log, register_log_callback  # noqa: F401
from .timer import Timer  # noqa: F401
