"""Feature export formats.

≙ reference export surface (tools/export/formats/ExportFormat.scala: arrow/
avro/bin/csv/geojson/gml/json/leaflet/orc/parquet/shp/tsv/wkt) — every
format the reference CLI exports is covered: csv/tsv, geojson, json-lines,
wkt, arrow IPC, parquet, avro, orc, gml, shp (ESRI shapefile), a
self-contained leaflet HTML map, npz (the checkpoint codec), and bin via
aggregates.bin."""

from __future__ import annotations

import csv
import io
import json
from typing import Optional

import numpy as np

from geomesa_tpu.features.geometry import GeometryArray
from geomesa_tpu.features.table import FeatureTable, StringColumn

FORMATS = ("csv", "tsv", "geojson", "json", "wkt", "arrow", "parquet",
           "avro", "orc", "gml", "shp", "leaflet")


def export(table: FeatureTable, fmt: str, path: Optional[str] = None):
    """Write ``table`` in ``fmt`` to ``path`` (or return a str for text
    formats when path is None)."""
    fmt = fmt.lower()
    if fmt in ("csv", "tsv"):
        return _delimited(table, "," if fmt == "csv" else "\t", path)
    if fmt == "geojson":
        return _geojson(table, path)
    if fmt == "json":
        return _jsonlines(table, path)
    if fmt == "wkt":
        return _wkt(table, path)
    if fmt == "arrow":
        from geomesa_tpu.io.arrow import write_ipc
        if path is None:
            raise ValueError("arrow export requires a path")
        write_ipc(table, path)
        return path
    if fmt == "avro":
        from geomesa_tpu.convert.avro import write_avro
        if path is None:
            raise ValueError("avro export requires a path")
        write_avro(table, path)
        return path
    if fmt == "parquet":
        import pyarrow.parquet as pq
        from geomesa_tpu.io.arrow import to_arrow
        if path is None:
            raise ValueError("parquet export requires a path")
        pq.write_table(to_arrow(table), path)
        return path
    if fmt == "orc":
        from pyarrow import orc
        from geomesa_tpu.io.arrow import to_arrow, orc_compatible
        if path is None:
            raise ValueError("orc export requires a path")
        orc.write_table(orc_compatible(to_arrow(table)), path)
        return path
    if fmt == "gml":
        return _gml(table, path)
    if fmt == "leaflet":
        return _leaflet(table, path)
    if fmt == "shp":
        if path is None:
            raise ValueError("shp export requires a path (base name)")
        return _shapefile(table, path)
    raise ValueError(f"Unknown export format {fmt!r} (have {FORMATS})")


def join_answer(op: str, polygons: FeatureTable, counts, sums: dict) -> dict:
    """The body of a join: a row a feature of the polygon table, in its
    order, a polygon that matched nothing with zeros. ``counts``: (P,)
    integers; ``sums``: attribute → (P,) integers."""
    name = polygons.columns.get("name")
    if isinstance(name, StringColumn):
        vocab = name.vocab
        name = [vocab[c] if c >= 0 else None
                for c in np.asarray(name.codes).tolist()]
    rows = []
    for i, (fid, n) in enumerate(zip(polygons.fids,
                                     np.asarray(counts).tolist())):
        rows.append({"fid": fid, "name": None if name is None else name[i],
                     "count": n,
                     "sum": {a: int(v[i]) for a, v in sums.items()}})
    return {"op": op, "polygons": len(rows), "rows": rows}


def _out(path: Optional[str]):
    return open(path, "w", newline="") if path else io.StringIO()


def _finish(f, path):
    if path:
        f.close()
        return path
    return f.getvalue()


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


def _delimited(table: FeatureTable, delim: str, path):
    f = _out(path)
    w = csv.writer(f, delimiter=delim)
    attrs = table.sft.attributes
    w.writerow(["id"] + [a.name for a in attrs])
    cols = [table.columns[a.name] for a in attrs]
    for i in range(len(table)):
        w.writerow([table.fids[i]] + [_cell(c, a, i) for c, a in zip(cols, attrs)])
    return _finish(f, path)


def _geojson_geometry(garr: GeometryArray, i: int) -> dict:
    from geomesa_tpu.features import geometry as geo
    code, data = garr.shape(i)
    return {"type": geo.TYPE_NAMES[code], "coordinates": data}


def _geojson(table: FeatureTable, path):
    garr = table.geometry() if table.sft.geometry_attribute else None
    gname = table.sft.geometry_attribute.name if garr is not None else None
    feats = []
    for i in range(len(table)):
        props = {}
        for a in table.sft.attributes:
            if a.name == gname:
                continue
            props[a.name] = _cell(table.columns[a.name], a, i)
        feats.append({
            "type": "Feature",
            "id": str(table.fids[i]),
            "geometry": None if garr is None else _geojson_geometry(garr, i),
            "properties": props,
        })
    doc = {"type": "FeatureCollection", "features": feats}
    f = _out(path)
    json.dump(doc, f)
    return _finish(f, path)


def _jsonlines(table: FeatureTable, path):
    f = _out(path)
    for row in table.to_dicts():
        json.dump({k: (v.item() if isinstance(v, np.generic) else v)
                   for k, v in row.items()}, f)
        f.write("\n")
    return _finish(f, path)


def _wkt(table: FeatureTable, path):
    garr = table.geometry()
    f = _out(path)
    for i in range(len(table)):
        f.write(garr.wkt(i) + "\n")
    return _finish(f, path)


# -- GML (Geography Markup Language; ≙ ExportFormat.Gml / GML3 encoder) ------


def _gml_coords(pts) -> str:
    return " ".join(f"{float(p[0])!r} {float(p[1])!r}" for p in pts)


def _gml_geometry(code: int, data) -> str:
    from geomesa_tpu.features import geometry as geo
    srs = ' srsName="urn:ogc:def:crs:EPSG::4326"'
    if code == geo.POINT:
        return (f"<gml:Point{srs}><gml:pos>{float(data[0])!r} "
                f"{float(data[1])!r}</gml:pos></gml:Point>")
    if code == geo.LINESTRING:
        return (f"<gml:LineString{srs}><gml:posList>{_gml_coords(data)}"
                "</gml:posList></gml:LineString>")
    if code == geo.POLYGON:
        rings = [f"<gml:{tag}><gml:LinearRing><gml:posList>"
                 f"{_gml_coords(r)}</gml:posList></gml:LinearRing></gml:{tag}>"
                 for r, tag in zip(data, ["exterior"]
                                   + ["interior"] * (len(data) - 1))]
        return f"<gml:Polygon{srs}>{''.join(rings)}</gml:Polygon>"
    if code == geo.MULTIPOINT:
        members = "".join(f"<gml:pointMember>{_gml_geometry(geo.POINT, p)}"
                          "</gml:pointMember>" for p in data)
        return f"<gml:MultiPoint{srs}>{members}</gml:MultiPoint>"
    if code == geo.MULTILINESTRING:
        members = "".join(
            f"<gml:curveMember>{_gml_geometry(geo.LINESTRING, l)}"
            "</gml:curveMember>" for l in data)
        return f"<gml:MultiCurve{srs}>{members}</gml:MultiCurve>"
    if code == geo.MULTIPOLYGON:
        members = "".join(
            f"<gml:surfaceMember>{_gml_geometry(geo.POLYGON, p)}"
            "</gml:surfaceMember>" for p in data)
        return f"<gml:MultiSurface{srs}>{members}</gml:MultiSurface>"
    raise ValueError(f"Unsupported geometry code {code}")


def _gml(table: FeatureTable, path):
    from xml.sax.saxutils import escape, quoteattr
    sft = table.sft
    gname = sft.geometry_attribute.name if sft.geometry_attribute else None
    garr = table.geometry() if gname else None
    f = _out(path)
    f.write('<?xml version="1.0" encoding="UTF-8"?>\n')
    f.write('<gml:FeatureCollection '
            'xmlns:gml="http://www.opengis.net/gml/3.2" '
            'xmlns:gt="urn:geomesa-tpu">\n')
    for i in range(len(table)):
        f.write(f' <gml:featureMember>\n  <gt:{sft.name} '
                f'gml:id={quoteattr(str(table.fids[i]))}>\n')
        for a in sft.attributes:
            if a.name == gname:
                code, data = garr.shape(i)
                f.write(f"   <gt:{a.name}>{_gml_geometry(code, data)}"
                        f"</gt:{a.name}>\n")
            else:
                v = _cell(table.columns[a.name], a, i)
                f.write(f"   <gt:{a.name}>{escape(str(v))}</gt:{a.name}>\n")
        f.write(f"  </gt:{sft.name}>\n </gml:featureMember>\n")
    f.write("</gml:FeatureCollection>\n")
    return _finish(f, path)


# -- ESRI shapefile (.shp/.shx/.dbf; ≙ ExportFormat.Shp) ---------------------
# Wire layouts per the public ESRI whitepaper; the reader counterpart lives
# in convert/formats.py (read_shapefile) and round-trips these files.


def _ring_area(pts) -> float:
    a = 0.0
    for i in range(len(pts) - 1):
        a += pts[i][0] * pts[i + 1][1] - pts[i + 1][0] * pts[i][1]
    return a / 2.0


def _shp_record(code: int, data):
    """(shape_type, content bytes after the type word) for one geometry."""
    import struct
    from geomesa_tpu.features import geometry as geo

    def parts_record(shape_type, parts):
        pts = [p for part in parts for p in part]
        xs = [p[0] for p in pts]
        ys = [p[1] for p in pts]
        head = struct.pack("<4d", min(xs), min(ys), max(xs), max(ys))
        head += struct.pack("<ii", len(parts), len(pts))
        off = 0
        for part in parts:
            head += struct.pack("<i", off)
            off += len(part)
        body = b"".join(struct.pack("<dd", float(p[0]), float(p[1]))
                        for p in pts)
        return shape_type, head + body

    if code == geo.POINT:
        return 1, struct.pack("<dd", float(data[0]), float(data[1]))
    if code == geo.MULTIPOINT:
        xs = [p[0] for p in data]
        ys = [p[1] for p in data]
        head = struct.pack("<4d", min(xs), min(ys), max(xs), max(ys))
        head += struct.pack("<i", len(data))
        body = b"".join(struct.pack("<dd", float(p[0]), float(p[1]))
                        for p in data)
        return 8, head + body
    if code == geo.LINESTRING:
        return parts_record(3, [data])
    if code == geo.MULTILINESTRING:
        return parts_record(3, data)
    if code in (geo.POLYGON, geo.MULTIPOLYGON):
        polys = [data] if code == geo.POLYGON else data
        rings = []
        for poly in polys:
            for j, ring in enumerate(poly):
                # spec orientation: exterior clockwise (negative signed
                # area), holes counter-clockwise
                cw = _ring_area(ring) < 0
                want_cw = j == 0
                rings.append(list(ring) if cw == want_cw else list(ring)[::-1])
        return parts_record(5, rings)
    raise ValueError(f"Unsupported geometry code {code} for shapefile")


def _dbf_fields(sft):
    """(name, type, width, decimals, formatter) per non-geometry attr."""
    out = []
    taken = set()
    for a in sft.attributes:
        if a.is_geometry:
            continue
        # DBF names are 10 chars: unique the truncations or the reader
        # merges colliding columns into interleaved garbage. Loop because a
        # renamed candidate can itself collide (attribute1/attribute12)
        base10 = a.name[:10]
        name, k = base10, 0
        while name in taken:
            k += 1
            name = f"{base10[:10 - len(str(k))]}{k}"
        taken.add(name)
        if a.type_name in ("Int", "Integer", "Long"):
            # width 20 holds any int64 incl. the sign; never slice digits
            out.append((name, b"N", 20, 0,
                        lambda v: f"{int(v):>20d}"))
        elif a.type_name in ("Float", "Double"):
            out.append((name, b"F", 19, 11,
                        lambda v: f"{float(v):>19.11g}"[:19].rjust(19)))
        elif a.type_name == "Date":
            out.append((name, b"D", 8, 0,
                        lambda v: str(np.datetime64(int(v), "ms"))[:10]
                        .replace("-", "")))
        elif a.type_name == "Boolean":
            out.append((name, b"L", 1, 0,
                        lambda v: "T" if v else "F"))
        else:
            out.append((name, b"C", 64, 0,
                        lambda v: str(v)[:64].ljust(64)))
    return out


def _shapefile(table: FeatureTable, path: str) -> str:
    """Write ``path``.shp/.shx/.dbf. Geometry column required."""
    import os
    import struct

    base, ext = os.path.splitext(path)
    if ext not in ("", ".shp"):
        base = path
    garr = table.geometry()
    n = len(table)
    records = []
    shape_type = 0
    for i in range(n):
        st, content = _shp_record(*garr.shape(i))
        if shape_type == 0:
            shape_type = st
        elif st != shape_type:
            raise ValueError("shapefile export needs a single shape type "
                             f"(got {shape_type} and {st})")
        records.append(struct.pack("<i", st) + content)

    bbs = garr.bboxes()
    if n:
        bbox = (float(bbs[:, 0].min()), float(bbs[:, 1].min()),
                float(bbs[:, 2].max()), float(bbs[:, 3].max()))
    else:
        bbox = (0.0, 0.0, 0.0, 0.0)

    def header(total_words):
        return (struct.pack(">i", 9994) + b"\x00" * 20
                + struct.pack(">i", total_words)
                + struct.pack("<ii", 1000, shape_type)
                + struct.pack("<4d", *bbox) + struct.pack("<4d", 0, 0, 0, 0))

    shp_words = 50 + sum(4 + len(r) // 2 for r in records)
    with open(base + ".shp", "wb") as f:
        f.write(header(shp_words))
        offset = 50
        offsets = []
        for num, rec in enumerate(records, 1):
            f.write(struct.pack(">ii", num, len(rec) // 2) + rec)
            offsets.append((offset, len(rec) // 2))
            offset += 4 + len(rec) // 2
    with open(base + ".shx", "wb") as f:
        f.write(header(50 + 4 * n))
        for off, words in offsets:
            f.write(struct.pack(">ii", off, words))

    fields = _dbf_fields(table.sft)
    rec_size = 1 + sum(w for _, _, w, _, _ in fields)
    attrs = [a for a in table.sft.attributes if not a.is_geometry]
    with open(base + ".dbf", "wb") as f:
        import datetime
        today = datetime.date.today()
        hdr_size = 32 + 32 * len(fields) + 1
        # header date bytes are (years since 1900, month, day)
        f.write(struct.pack("<BBBBIHH20x", 3, today.year - 1900, today.month,
                            today.day, n, hdr_size, rec_size))
        for name, typ, width, dec, _fmt in fields:
            f.write(name.encode("ascii", "replace")[:11].ljust(11, b"\x00")
                    + typ + b"\x00" * 4
                    + struct.pack("<BB", width, dec) + b"\x00" * 14)
        f.write(b"\x0d")
        for i in range(n):
            row = b" "
            for (name, typ, width, dec, fmt), a in zip(fields, attrs):
                v = _cell(table.columns[a.name], a, i)
                if a.type_name == "Date":
                    v = int(np.asarray(table.columns[a.name])[i])
                row += fmt(v).encode("ascii", "replace")[:width].ljust(width)
            f.write(row)
        f.write(b"\x1a")
    return base + ".shp"


# -- Leaflet map (self-contained HTML; ≙ LeafletMapExporter) -----------------


_LEAFLET_HTML = """<!DOCTYPE html>
<html>
<head>
<meta charset="utf-8"/>
<title>geomesa-tpu export</title>
<meta name="viewport" content="width=device-width, initial-scale=1.0"/>
<link rel="stylesheet"
 href="https://unpkg.com/leaflet@1.9.4/dist/leaflet.css"/>
<script src="https://unpkg.com/leaflet@1.9.4/dist/leaflet.js"></script>
<style>html, body, #map {{ height: 100%; margin: 0; }}</style>
</head>
<body>
<div id="map"></div>
<script>
var features = {geojson};
var map = L.map('map');
L.tileLayer('https://{{s}}.tile.openstreetmap.org/{{z}}/{{x}}/{{y}}.png',
            {{attribution: '&copy; OpenStreetMap contributors'}}).addTo(map);
var layer = L.geoJSON(features, {{
  pointToLayer: function (f, latlng) {{
    return L.circleMarker(latlng, {{radius: 4}});
  }},
  onEachFeature: function (f, l) {{
    var esc = function (s) {{
      return String(s).replace(/&/g, '&amp;').replace(/</g, '&lt;')
                      .replace(/>/g, '&gt;').replace(/"/g, '&quot;');
    }};
    var rows = Object.entries(f.properties || {{}}).map(
      function (kv) {{ return esc(kv[0]) + ': ' + esc(kv[1]); }});
    if (rows.length) l.bindPopup(rows.join('<br/>'));
  }}
}}).addTo(map);
var b = layer.getBounds();
if (b.isValid()) {{ map.fitBounds(b); }} else {{ map.setView([0, 0], 2); }}
</script>
</body>
</html>
"""


def _leaflet(table: FeatureTable, path):
    """Self-contained HTML map with the features embedded as GeoJSON (the
    tile layer loads from OSM in the viewer's browser, as the reference's
    template does). The embedded JSON escapes '</' so a string value
    containing '</script>' can neither break the document nor inject
    script; popup values HTML-escape browser-side."""
    geojson = _geojson(table, None).replace("</", "<\\/")
    doc = _LEAFLET_HTML.format(geojson=geojson)
    f = _out(path)
    f.write(doc)
    return _finish(f, path)
