"""From the start of the window's `fit` span to the first run of the train
step's program on the device: the pipeline's fill, during which the chip
waits for the feed's first batches. The span is on the program's clock and
the run on the device trace's; the five beacons of the traced run link
the two (`span_reduce.clock_link`). No value without a device trace, a
link or a span store."""

from benchmarks import span_reduce


def read(facts):
    if facts["trace"] is None or facts["clock"] is None \
            or not facts["spans"]:
        return None
    _, runs = span_reduce.main_module(facts["xspace"],
                                      facts["trace"]["devices"])
    return span_reduce.fill_ms(facts["spans"], runs,
                               facts["clock"]["zero_ns"])
