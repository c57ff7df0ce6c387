"""Device milliseconds per fit in the device GDI's programs (the "init"
group, program_groups/init.json)."""


def read(ctx):
    s = ctx["summary"]
    t = s.group_s.get("init")
    return None if not t else 1e3 * t / len(s.spans)
