"""How many of set-up's `xla.compile` spans were real compiles (`fetched`
false): 0 in a warm run, which is what says whether a `setup_s` reading
was warm. The probe's and the beacon's are left out. No value from a
program that records no compile."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("xla_compiles.setup")
