"""Set-up's `import.*` spans, the union of their intervals: the time the
package's own `__init__.py` files spent importing (JAX and Pallas too
where one of them is the first to ask; the step's first trace imports
`ops` lazily, which counts here as well). Read out of the program's span
store through `benchmarks/setup_spans.py`; no value from a program that
does not time its set-up or once the ring has wrapped."""

from benchmarks import setup_spans


def read(facts):
    return setup_spans.read("import_ms.setup")
