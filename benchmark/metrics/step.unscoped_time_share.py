"""Share of device 0's busy time in instructions the program's op index
puts under no scope (an instruction the index lacks among them): the bound
on every other share of the scope tree. The line's ``breakdown`` names the
largest ops; ``scope_report.json`` of an ``SMP_PROFILE`` capture names the
ten largest of these."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, lambda record: record["unscoped"]["seconds"])
