"""``runtime.peak_device_gib`` for served cells (moves ``served_gpts_per_s``)."""

from metric_alias import reader

read = reader("runtime.peak_device_gib")
