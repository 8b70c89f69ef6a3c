"""1 - union of device-op intervals / traced window, in %."""

from benchmark.readers import device_idle as read  # noqa: F401
