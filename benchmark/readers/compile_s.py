"""Seconds in backend compiles, from the program's compile ledger."""


def read(ctx):
    led = ctx["ledger"]
    if led is None or not led.get("compile_ms_total"):
        return None
    return led["compile_ms_total"] / 1e3
