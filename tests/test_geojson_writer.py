"""The columnar GeoJSON writer (``io/export.py:geojson``) against the plain
reference it replaced: a dict a feature, one value a cell, encoded by
``json.dump``. For every attribute type ``SimpleFeatureTypes`` accepts, with
the values at their edges and the nulls a column can hold, the two parse to
the same document."""

import io
import json
import math
import uuid

import numpy as np
import pytest

from geomesa_tpu.features import geometry as geo
from geomesa_tpu.features.geometry import GeometryArray
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.features.table import FeatureTable, StringColumn
from geomesa_tpu.io.export import export, geojson
from geomesa_tpu.metrics import REGISTRY


# -- the plain reference: a dict a feature, one value a cell -----------------

def _iso(ms: int) -> str:
    return str(np.datetime64(int(ms), "ms")) + "Z"


def _cell(col, attr, i):
    if isinstance(col, GeometryArray):
        return col.wkt(i)
    if isinstance(col, StringColumn):
        return col.vocab[col.codes[i]]
    v = col[i]
    if attr.type_name == "Date":
        return _iso(int(v))
    return v.item() if isinstance(v, np.generic) else v


def reference(table: FeatureTable) -> str:
    garr = table.geometry() if table.sft.geometry_attribute else None
    gname = table.sft.geometry_attribute.name if garr is not None else None
    feats = []
    for i in range(len(table)):
        props = {}
        for a in table.sft.attributes:
            if a.name == gname:
                continue
            props[a.name] = _cell(table.columns[a.name], a, i)
        geometry = None
        if garr is not None:
            code, data = garr.shape(i)
            geometry = {"type": geo.TYPE_NAMES[code], "coordinates": data}
        feats.append({"type": "Feature", "id": str(table.fids[i]),
                      "geometry": geometry, "properties": props})
    f = io.StringIO()
    json.dump({"type": "FeatureCollection", "features": feats}, f)
    return f.getvalue()


def reference_jsonlines(table: FeatureTable) -> str:
    f = io.StringIO()
    for row in table.to_dicts():
        json.dump({k: (v.item() if isinstance(v, np.generic) else v)
                   for k, v in row.items()}, f)
        f.write("\n")
    return f.getvalue()


# -- the cases ---------------------------------------------------------------

STRINGS = ['plain', 'say "hi"', "back\\slash", "tab\tnew\nline\r\x00\x1f",
           "café", "中文", "\U0001F600", "  ",
           "</script>", "", "100%s"]
FIDS = ['a"b', "c\\d", "e%sf", "ü", "g h", "i", "j", "k", "l", "m",
        "n"]


def _table(spec, data, fids=None):
    sft = SimpleFeatureType.from_spec("t", spec)
    return FeatureTable.build(sft, data, fids=fids)


def _gdelt_like(n=64, seed=5):
    """The served select's record: eight strings, four integers, a date and
    a point."""
    rng = np.random.default_rng(seed)
    spec = ("GLOBALEVENTID:String,Actor1Name:String,Actor1CountryCode:String,"
            "Actor2Name:String,Actor2CountryCode:String,EventCode:String,"
            "NumMentions:Integer,NumSources:Integer,NumArticles:Integer,"
            "ActionGeo_Type:Integer,ActionGeo_FullName:String,"
            "ActionGeo_CountryCode:String,dtg:Date,*geom:Point:srid=4326")
    sft = SimpleFeatureType.from_spec("gdelt", spec)
    data = {}
    for a in sft.attributes:
        if a.type_name == "String":
            data[a.name] = [f"{a.name[:3]}-{v}"
                            for v in rng.integers(0, 9, n)]
        elif a.type_name == "Integer":
            data[a.name] = rng.integers(0, 1000, n).astype(np.int32)
    data["dtg"] = (np.datetime64("2020-01-01", "ms").astype(np.int64)
                   + rng.integers(0, 30 * 86_400_000, n))
    data["geom"] = (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))
    return FeatureTable.build(sft, data).take(rng.permutation(n)[: n // 2])


def _cases():
    n = len(STRINGS)
    pt = (np.linspace(-179.5, 179.5, n), np.linspace(-89.9, 89.9, n))
    ms = [-1, -86_400_001, 0, 1_234_567_890_123, -62_135_596_800_000,
          253_402_300_799_999, 253_402_300_800_000, 1, 999, -999,
          np.iinfo(np.int64).min]
    floats = [0.1, math.nan, math.inf, -math.inf, -0.0, 1e308, 5e-324,
              0.1 + 0.2, 1 / 3, -2.5, 1e16]
    floats32 = [3.4e38 if v == 1e308 else 1e-45 if v == 5e-324 else v
                for v in floats]
    ids = [str(uuid.UUID(int=i * 7919 + 1)) for i in range(n)]
    return {
        "String": ("s:String,*g:Point", {"s": STRINGS, "g": pt}, FIDS),
        "Int": ("v:Int,*g:Point", {"v": np.array(
            [np.iinfo(np.int32).min, np.iinfo(np.int32).max, 0, -1, 7, 8, 9,
             10, 11, 12, 13], np.int32), "g": pt}, None),
        "Integer": ("v:Integer", {"v": np.arange(-5, n - 5)}, None),
        "Long": ("v:Long,*g:Point", {"v": np.array(
            [np.iinfo(np.int64).min, np.iinfo(np.int64).max, 2 ** 53 + 1,
             -(2 ** 40), 0, 1, 2, 3, 4, 5, 6], np.int64), "g": pt}, None),
        "Float": ("v:Float,*g:Point", {"v": floats32, "g": pt}, None),
        "Double": ("v:Double,*g:Point", {"v": floats, "g": pt}, None),
        "Double-null": ("v:Double", {"v": [1.5, None] * 5 + [None]}, None),
        "Boolean": ("v:Boolean,*g:Point",
                    {"v": [True, False] * 5 + [True], "g": pt}, None),
        "Boolean-null": ("v:Boolean", {"v": [True, None] * 5 + [None]}, None),
        "Date": ("d:Date,*g:Point", {"d": np.array(ms, np.int64), "g": pt},
                 None),
        "UUID": ("u:UUID,*g:Point", {"u": ids, "g": pt}, None),
        "UUID-null": ("u:UUID", {"u": ids[:5] + [None] * 6}, None),
        "Bytes": ("b:Bytes,*g:Point", {"b": STRINGS, "g": pt}, None),
        "Bytes-null": ("b:Bytes", {"b": [None, "x"] * 5 + [None]}, None),
        "LineString": ("*g:LineString", {"g": [
            "LINESTRING (0 0, 1.5 2.25, -3 4)", "LINESTRING (10 10, 11 11)"]},
            None),
        "Polygon": ("n:String,*g:Polygon", {"n": ["a", "b"], "g": [
            "POLYGON ((0 0, 10 0, 10 10, 0 10, 0 0), "
            "(2 2, 3 2, 3 3, 2 2))",
            "POLYGON ((0.1 0.2, 1 0, 1 1, 0.1 0.2))"]}, None),
        "Multi": ("*g:Geometry", {"g": [
            "MULTIPOINT ((1 2), (3 4))",
            "MULTILINESTRING ((0 0, 1 1), (2 2, 3 3, 4 5))",
            "MULTIPOLYGON (((0 0, 1 0, 1 1, 0 0)), "
            "((5 5, 6 5, 6 6, 5 5), (5.2 5.1, 5.3 5.1, 5.3 5.2, 5.2 5.1)))",
            "POINT (7 8)"]}, None),
        "second-geometry": ("*g:Point,line:LineString", {
            "g": (np.array([1.0, 2.0]), np.array([3.0, 4.0])),
            "line": ["LINESTRING (0 0, 1 1)", "LINESTRING (2 2, 3 4)"]},
            ["p%s", 'q"']),
        "no-geometry": ("pct%s:String,q\"k:Integer,d:Date", {
            "pct%s": ["x", "y"], "q\"k": [1, 2], "d": [0, 86_400_000]},
            None),
        "empty": ("s:String,v:Double,d:Date,*g:Point", {
            "s": [], "v": np.zeros(0), "d": np.zeros(0, np.int64),
            "g": (np.zeros(0), np.zeros(0))}, None),
    }


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES) + ["gdelt-like"])
def test_writer_parses_to_the_reference(name):
    if name == "gdelt-like":
        t = _gdelt_like()
    else:
        spec, data, fids = CASES[name]
        t = _table(spec, data, fids)
    got, want = geojson(t), reference(t)
    assert got.isascii()
    g, w = json.loads(got), json.loads(want)
    # NaN != NaN: compare the documents' text once parsed and written again
    # by one encoder, which holds floats to the bit and the key order
    assert json.dumps(g) == json.dumps(w)
    assert export(t, "geojson") == got
    if name == "Bytes":
        # json-lines of the same table, the dump-per-row repair: no change
        assert export(t, "json") == reference_jsonlines(t)


def test_raw_bytes_are_refused_as_before():
    """A Bytes column of raw ``bytes`` was no JSON for the reference, and is
    none for the writer."""
    t = _table("b:Bytes", {"b": [b"\x00ab", b"c"]})
    with pytest.raises(TypeError):
        reference(t)
    with pytest.raises(TypeError):
        geojson(t)


def test_counters_name_how_each_column_was_encoded():
    def counts():
        c = REGISTRY.snapshot()["counters"]
        return (c.get("export.geojson.columns_whole", 0),
                c.get("export.geojson.columns_by_value", 0))

    before = counts()
    geojson(_gdelt_like())
    after = counts()
    assert (after[0] - before[0], after[1] - before[1]) == (14, 0)
    spec, data, fids = CASES["second-geometry"]
    geojson(_table(spec, data, fids))
    last = counts()
    assert (last[0] - after[0], last[1] - after[1]) == (1, 1)
