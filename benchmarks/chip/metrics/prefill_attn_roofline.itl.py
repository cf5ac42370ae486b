"""prefill_attn_roofline.itl: the chunked-prefill kernel's share of its
roofline, read as ``prefill_attn_roofline.online`` reads it, in a cell
where the ticks that carry a chunk set the tail of the gaps between
tokens."""

from chipbench import window

read = window.reader("prefill_attn_roofline.online")
