"""Share of the traced window in which no op ran on device 0."""

from benchmark import loader

read = loader.load_sibling(__file__, "_shared").idle_share
