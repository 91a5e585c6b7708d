"""A configuration's family module is found by its file's ``reference``
key, and what no family module can compare is refused before a run."""

import json

import pytest

from bench import model
from bench.tests import tiny


def _write(tmp_path, **changes):
    raw = json.loads(json.dumps(tiny.CONFIG))
    for key, value in changes.items():
        section, _, leaf = key.partition("__")
        if leaf:
            raw[section][leaf] = value
        else:
            raw[section] = value
    path = tmp_path / "conf.json"
    path.write_text(json.dumps(raw))
    return path


def test_the_family_named_in_the_file_reads_it(tmp_path):
    spec = model.load_spec("tiny", _write(tmp_path))
    assert spec.reference == "dense" and spec.kv_dtype == "bf16"
    assert model.family(spec.reference).load_spec is not None
    assert model.engine(spec.reference).read_pool is not None


@pytest.mark.parametrize("changes,what", [
    ({"reference": "moe"}, "no reference module for the family 'moe'"),
    ({"deployment__kv_dtype": "int8"}, "compares a KV cache"),
    ({"quant__act_group": 16}, "per-token W4A4 only"),
], ids=["family", "kv_dtype", "act_group"])
def test_what_cannot_be_compared_is_refused(tmp_path, changes, what):
    with pytest.raises(ValueError, match=what):
        model.load_spec("tiny", _write(tmp_path, **changes))
