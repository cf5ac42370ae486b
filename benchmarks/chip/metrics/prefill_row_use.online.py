"""prefill_row_use.online: prompt tokens prefilled over the rows that
the window's chunk steps computed (every slot at the tick's chunk
bucket), in percent."""


def read(w):
    used, rows = w.prefill_rows()
    return 100.0 * used / rows if rows else None
