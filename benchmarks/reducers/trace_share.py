"""``trace_share``: own device time of the operations that match
``pattern`` (on their label, or with ``"on": "text"`` their whole HLO
text), as a share of device busy time (first chip), in percent.
``pattern`` may use the placeholders the runner gives, such as
``{kv_pool}``: the KV pool's dimensions as a label spells them."""

from ..layer_metrics import fill


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None or not reduction.busy_first_s:
        return None
    pattern = fill(spec["pattern"], evidence)
    if pattern is None:
        return None
    return 100.0 * reduction.seconds_matching(
        pattern, spec.get("on", "label")) / reduction.busy_first_s
