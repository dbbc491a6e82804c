"""chipbench/tests fixture: a per-layer metric that exists only in
the fixture manifest — adding it took this file and one manifest
entry, and no edit to a file that was there."""


def read(ctx, raw):
    return raw.get("steps")
