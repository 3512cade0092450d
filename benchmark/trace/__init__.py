"""From the profiler's ``.xplane.pb`` to device metrics."""
