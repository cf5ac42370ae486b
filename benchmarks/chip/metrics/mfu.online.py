"""mfu.online: useful model FLOPs of the window (decoded tokens with the
top-k experts they route to, prefilled prompt positions) over the
window's seconds times the chip's peak, in percent, read as ``mfu.batch``
reads it, in an open-loop cell."""

from chipbench import window

read = window.reader("mfu.batch")
