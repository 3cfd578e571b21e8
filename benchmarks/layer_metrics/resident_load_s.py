"""storage: the part of set-up spent loading tables into the
device-resident store: ``resident_load_wall_us`` of /metrics at the
window's start (a lifetime total of the server's catalogs: every
table's first touch, start to the last byte on the device), in
seconds. The warm-up serves every statement before the window, so the
loads lie inside ``setup_s``. A program without the counter, or a cell
that stores no table, gives nothing to read."""


def read(ctx):
    us = ctx["metrics_start"].get("resident_load_wall_us")
    return us / 1e6 if us else None
