"""host_ms_per_tick.online: host time of one scheduler tick outside its
waits for the device, read as ``host_ms_per_tick.batch`` reads it, in an
open-loop cell."""

from chipbench import window

read = window.reader("host_ms_per_tick.batch")
