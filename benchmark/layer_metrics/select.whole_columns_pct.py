"""Of the columns the GeoJSON writer encoded, the share it encoded as a
whole column, in percent: where it did not, it encoded value by value.

layer: REST (io/export.py's ``geojson``) · source: program_counter · moves:
qps
Counters ``export.geojson.columns_whole`` (a column's values made JSON text
in one pass: numbers and booleans through one call of the C encoder, a
string column's distinct values once, dates as one array, points from the
coordinate planes) over it plus ``export.geojson.columns_by_value`` (a
column encoded value by value: other geometries, UUID, Bytes), ``before`` →
``after``. The record of ``gdelt-z3-10m`` reads 100. A program without the
counters (one that builds a dict a feature) reads None, as does a window
that wrote no GeoJSON."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _window  # noqa: E402


def read(ctx: dict):
    whole = _window.counter_delta(ctx, "export.geojson.columns_whole")
    by_value = _window.counter_delta(ctx, "export.geojson.columns_by_value")
    if whole is None or by_value is None or whole + by_value <= 0:
        return None
    return 100.0 * whole / (whole + by_value)
