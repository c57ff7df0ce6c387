"""Device milliseconds per fit in the exact start, the resident layout
build and the bounded iteration step with its Pallas kernels (the
"iterate" group, program_groups/iterate.json)."""


def read(ctx):
    s = ctx["summary"]
    t = s.group_s.get("iterate")
    return None if not t else 1e3 * t / len(s.spans)
