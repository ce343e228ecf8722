"""Bytes read from the value and index logs per get completed in the
window (``bytes_read_disk`` counts every pread of both logs)."""


def read(ctx):
    gets = ctx["done"].get("get", 0)
    if not gets:
        return None
    return ctx["db"]["bytes_read_disk"] / gets
