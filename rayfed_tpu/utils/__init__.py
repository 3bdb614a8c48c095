from rayfed_tpu.utils.validation import validate_address, validate_cluster_info
from rayfed_tpu.utils.logging_utils import setup_logger
from rayfed_tpu.utils.platform import force_cpu_devices, use_compilation_cache

__all__ = [
    "validate_address",
    "validate_cluster_info",
    "setup_logger",
    "force_cpu_devices",
    "use_compilation_cache",
]
