"""``device.longest_gap_ms`` for served cells (moves ``served_gpts_per_s``)."""

from metric_alias import reader

read = reader("device.longest_gap_ms")
