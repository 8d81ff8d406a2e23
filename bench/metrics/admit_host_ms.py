"""Host time per admission outside its wait for the chip (serving cells).

Mean, over the engine's ``serving.admit`` spans that start inside the
traced window, of the span's duration less that of its
``serving.prefill.wait`` descendant: the slot's set-up, the prompt's upload
and the prefill's dispatch that the host runs for each admitted request
(program spans of ``repro.serving.engine``; shared reading in
``decode_host_ms``).
"""

import harness


def read(r):
    spans = harness.load_module("metrics", "decode_host_ms")
    found = spans.program_spans(r)
    if found is None:
        return None
    return spans.host_ms(found, "serving.admit", "serving.prefill.wait")
