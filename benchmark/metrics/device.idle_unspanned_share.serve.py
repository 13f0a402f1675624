"""``device.idle_unspanned_share`` for served cells (moves
``served_gpts_per_s``)."""

from metric_alias import reader

read = reader("device.idle_unspanned_share")
