"""End to end, served cells: the same quotient as ``gpts_per_s`` over
whole served requests, the clock at the client around ``srv.run``."""

from metric_alias import reader

read = reader("gpts_per_s")
