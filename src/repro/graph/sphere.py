"""GraphCast's spherical geometry, in host numpy [arXiv:2212.12794, §3].

* The icosahedral refinement hierarchy: each level splits every triangle
  into four at its edges' midpoints, projected onto the unit sphere. A
  level's vertices are a prefix of the next level's, and the children of
  face ``f`` are faces ``4f .. 4f+3`` of the next level, so the four tile
  their parent exactly (a midpoint of a great-circle arc lies on that arc).
* The multimesh: the finest level's vertices with the edges of every level
  from ``min_level`` up, each in both directions.
* The lat-lon grid: latitudes from -90 to 90 inclusive, longitudes from 0,
  at ``resolution`` degrees; grid node ``i_lat * n_lon + i_lon``.
* Grid2Mesh: every (grid node, mesh node) pair within ``radius_fraction``
  times the longest finest-level edge (chord lengths, as GraphCast's
  radius query measures them).
* Mesh2Grid: the three vertices of the finest-level triangle that contains
  each grid node, found by descending the hierarchy (20 tests at level 0,
  then 4 per level).
* Node features: the cosine of the colatitude (the sine of the latitude)
  and the cosine and sine of the longitude. The paper's text names the
  cosine of the latitude; its released code takes the colatitude's, which
  tells the hemispheres apart, and this module follows the code.
* Edge features: the sender-minus-receiver vector in the receiver's local
  frame (rotated so that the receiver lies at latitude 0, longitude 0), and
  its length, both over the longest edge of that edge set, so every length
  lies in [0, 1].

Edge arrays are ordered by receiver, then sender. Each edge set carries
the layouts of `repro.graph.ops.sorted_segment_sum` for its receivers and,
through the permutation that orders its edges by sender, for its senders.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.graph.ops import sorted_layout
from repro.obs import trace as _obs_trace

__all__ = [
    "TriMesh",
    "GraphCastGraph",
    "icosahedron",
    "refine",
    "mesh_hierarchy",
    "mesh_edges",
    "multimesh_edges",
    "latlon_grid",
    "latlon_to_xyz",
    "radius_edges",
    "containing_faces",
    "node_features",
    "edge_features",
    "build_graph",
    "graph_sizes",
    "graph_shapes",
]


@dataclasses.dataclass(frozen=True)
class TriMesh:
    vertices: np.ndarray        # (V, 3) float64, unit vectors
    faces: np.ndarray           # (F, 3) int64, counter-clockwise seen from outside


def icosahedron() -> TriMesh:
    """The regular icosahedron on the unit sphere, turned about the y axis
    so that a face lies flat at each pole: no vertex and no edge passes
    through a pole, where the grid's 360 pole nodes coincide."""
    phi = (1.0 + 5.0 ** 0.5) / 2.0
    v = []
    for a in (1.0, -1.0):
        for b in (phi, -phi):
            v += [(a, b, 0.0), (0.0, a, b), (b, 0.0, a)]
    v = np.asarray(v) / np.hypot(1.0, phi)
    # Two faces meet at the top edge, each tilted from the horizontal by
    # half of (pi - the dihedral angle); turning by that lays one flat.
    angle = (np.pi - 2.0 * np.arcsin(phi / np.sqrt(3.0))) / 2.0
    c, s = np.cos(angle), np.sin(angle)
    v = v @ np.array([[c, 0.0, -s], [0.0, 1.0, 0.0], [s, 0.0, c]])
    d = np.linalg.norm(v[:, None] - v[None], axis=-1)
    adj = np.isclose(d, d[d > 1e-9].min())
    faces = [(i, j, k) for i in range(12) for j in range(i + 1, 12) for k in range(j + 1, 12)
             if adj[i, j] and adj[j, k] and adj[i, k]]
    faces = np.asarray(faces, np.int64)
    a, b, c3 = v[faces[:, 0]], v[faces[:, 1]], v[faces[:, 2]]
    flip = np.einsum("ij,ij->i", np.cross(b - a, c3 - a), a + b + c3) < 0
    faces[flip] = faces[flip][:, [0, 2, 1]]
    return TriMesh(v, faces)


def refine(mesh: TriMesh) -> TriMesh:
    """Split each face (a, b, c) into (a, ab, ca), (ab, b, bc), (ca, bc, c),
    (ab, bc, ca): children of face f at rows 4f .. 4f+3; new vertices after
    the old ones."""
    f = mesh.faces
    n = mesh.vertices.shape[0]
    pairs = np.stack([f[:, [0, 1]], f[:, [1, 2]], f[:, [2, 0]]], axis=1).reshape(-1, 2)
    keys = np.sort(pairs, axis=1)
    uniq, inv = np.unique(keys, axis=0, return_inverse=True)
    mid = mesh.vertices[uniq[:, 0]] + mesh.vertices[uniq[:, 1]]
    mid /= np.linalg.norm(mid, axis=1, keepdims=True)
    m = (n + inv.reshape(-1)).reshape(-1, 3)             # ab, bc, ca per face
    a, b, c = f[:, 0], f[:, 1], f[:, 2]
    ab, bc, ca = m[:, 0], m[:, 1], m[:, 2]
    children = np.stack([np.stack([a, ab, ca], 1), np.stack([ab, b, bc], 1),
                         np.stack([ca, bc, c], 1), np.stack([ab, bc, ca], 1)], axis=1)
    return TriMesh(np.concatenate([mesh.vertices, mid]), children.reshape(-1, 3))


def mesh_hierarchy(splits: int) -> list[TriMesh]:
    """Levels 0 .. ``splits``: 10·4^r + 2 vertices and 20·4^r faces at r."""
    meshes = [icosahedron()]
    for _ in range(splits):
        meshes.append(refine(meshes[-1]))
    return meshes


def _by_receiver(senders: np.ndarray, receivers: np.ndarray):
    order = np.lexsort((senders, receivers))
    return senders[order].astype(np.int32), receivers[order].astype(np.int32)


def mesh_edges(faces: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Both directions of every edge of a closed triangle mesh."""
    s = faces.reshape(-1)
    r = faces[:, [1, 2, 0]].reshape(-1)
    s, r = np.concatenate([s, r]), np.concatenate([r, s])
    pairs = np.unique(np.stack([s, r], 1), axis=0)
    return pairs[:, 0], pairs[:, 1]


def multimesh_edges(meshes: list[TriMesh], min_level: int) -> tuple[np.ndarray, np.ndarray]:
    """The edges of levels ``min_level`` .. last over the last level's
    vertices. Edges of different levels join vertices a different number
    of finest steps apart, so none repeats: 2·30·Σ 4^r directed edges."""
    parts = [mesh_edges(m.faces) for m in meshes[min_level:]]
    return _by_receiver(np.concatenate([p[0] for p in parts]),
                        np.concatenate([p[1] for p in parts]))


def latlon_grid(resolution: float) -> tuple[np.ndarray, np.ndarray]:
    """(latitudes, longitudes) in degrees, poles included."""
    n_lat = int(round(180.0 / resolution)) + 1
    n_lon = int(round(360.0 / resolution))
    return np.linspace(-90.0, 90.0, n_lat), np.arange(n_lon) * resolution


def latlon_to_xyz(lat: np.ndarray, lon: np.ndarray) -> np.ndarray:
    la, lo = np.deg2rad(lat), np.deg2rad(lon)
    return np.stack([np.cos(la) * np.cos(lo), np.cos(la) * np.sin(lo), np.sin(la)], axis=-1)


def _xyz_to_latlon(xyz: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    lat = np.rad2deg(np.arcsin(np.clip(xyz[:, 2], -1.0, 1.0)))
    lon = np.rad2deg(np.arctan2(xyz[:, 1], xyz[:, 0]))
    return lat, lon


def radius_edges(grid_xyz: np.ndarray, mesh_xyz: np.ndarray, radius: float,
                 block: int = 1024) -> tuple[np.ndarray, np.ndarray]:
    """(grid senders, mesh receivers) of every pair whose chord is at most
    ``radius``: ``|g - m|² = 2 - 2 g·m``. Grid nodes go in blocks by
    latitude, each tested against the mesh nodes whose latitude lies within
    the radius's angle of the block's (a pair farther apart in latitude is
    farther apart on the sphere)."""
    floor = 1.0 - radius * radius / 2.0
    reach = 2.0 * np.arcsin(min(radius / 2.0, 1.0)) + 1e-9
    glat = np.arcsin(np.clip(grid_xyz[:, 2], -1.0, 1.0))
    mlat = np.arcsin(np.clip(mesh_xyz[:, 2], -1.0, 1.0))
    gorder, morder = np.argsort(glat, kind="stable"), np.argsort(mlat, kind="stable")
    msorted = mlat[morder]
    s, r = [], []
    for lo in range(0, grid_xyz.shape[0], block):
        gi = gorder[lo:lo + block]
        a = np.searchsorted(msorted, glat[gi].min() - reach, side="left")
        b = np.searchsorted(msorted, glat[gi].max() + reach, side="right")
        mi = morder[a:b]
        i, j = np.nonzero(grid_xyz[gi] @ mesh_xyz[mi].T >= floor)
        s.append(gi[i])
        r.append(mi[j])
    return _by_receiver(np.concatenate(s), np.concatenate(r))


def _inside(points: np.ndarray, vertices: np.ndarray, faces: np.ndarray) -> np.ndarray:
    """For each point and each of its candidate faces (``faces``: (P, K, 3)),
    the least of the three signed volumes ``det(a, b, p)``: non-negative
    exactly where the face's spherical triangle holds the point."""
    a, b, c = (vertices[faces[..., i]] for i in range(3))
    p = points[:, None, :]
    return np.minimum(np.minimum(np.einsum("pkd,pkd->pk", np.cross(a, b), p),
                                 np.einsum("pkd,pkd->pk", np.cross(b, c), p)),
                      np.einsum("pkd,pkd->pk", np.cross(c, a), p))


def containing_faces(points: np.ndarray, meshes: list[TriMesh]) -> np.ndarray:
    """Index, in the finest mesh, of a face whose triangle holds each point:
    at every level the child that holds it best (a point on a shared edge
    goes to either face)."""
    vertices = meshes[-1].vertices
    cand = np.broadcast_to(meshes[0].faces, (points.shape[0],) + meshes[0].faces.shape)
    face = np.argmax(_inside(points, vertices, cand), axis=1)
    for fine in meshes[1:]:
        kids = 4 * face[:, None] + np.arange(4)
        face = kids[np.arange(points.shape[0]), np.argmax(_inside(points, vertices, fine.faces[kids]), axis=1)]
    return face


def node_features(lat: np.ndarray, lon: np.ndarray) -> np.ndarray:
    """(n, 3) float32: cos(colatitude), cos(longitude), sin(longitude)."""
    la, lo = np.deg2rad(lat), np.deg2rad(lon)
    return np.stack([np.sin(la), np.cos(lo), np.sin(lo)], axis=-1).astype(np.float32)


def edge_features(sender_xyz: np.ndarray, receiver_xyz: np.ndarray,
                  receiver_lat: np.ndarray, receiver_lon: np.ndarray) -> np.ndarray:
    """(E, 4) float32: length, then the 3 components of sender − receiver in
    the receiver's local frame, all over the longest length of the set. The
    frame: turn by −longitude about z, then by +latitude about y."""
    la, lo = np.deg2rad(receiver_lat), np.deg2rad(receiver_lon)
    d = sender_xyz - receiver_xyz
    cz, sz = np.cos(-lo), np.sin(-lo)
    x1 = cz * d[:, 0] - sz * d[:, 1]
    y1 = sz * d[:, 0] + cz * d[:, 1]
    cy, sy = np.cos(la), np.sin(la)
    local = np.stack([cy * x1 + sy * d[:, 2], y1, -sy * x1 + cy * d[:, 2]], axis=-1)
    length = np.linalg.norm(d, axis=-1, keepdims=True)
    top = length.max()
    return (np.concatenate([length, local], axis=-1) / top).astype(np.float32)


def edge_layouts(name: str, senders: np.ndarray, receivers: np.ndarray,
                 n_senders: int, n_receivers: int) -> dict:
    """The sorted-sum layouts of one edge set (ordered by receiver), keyed
    as `GraphCastGraph.arrays` gives them: ``<name>_recv_{tiles,pairs}``
    over the receivers; ``<name>_send_order`` (the stable permutation into
    sender order) and ``<name>_send_{tiles,pairs}`` over the senders in
    that order."""
    order = np.argsort(senders, kind="stable")
    out = {f"{name}_send_order": order.astype(np.int32)}
    for side, ids, n in (("recv", receivers, n_receivers), ("send", senders[order], n_senders)):
        out.update(zip((f"{name}_{side}_{k}" for k in ("tiles", "pairs")),
                       sorted_layout(ids, n)))
    return out


@dataclasses.dataclass(frozen=True)
class GraphCastGraph:
    """The three graphs of one GraphCast model, host numpy. Index arrays
    are int32; each edge set is ordered by receiver."""

    mesh_xyz: np.ndarray        # (n_mesh, 3) float64
    grid_nodes: np.ndarray      # (n_grid, 3) float32 node features
    mesh_nodes: np.ndarray      # (n_mesh, 3)
    mesh_senders: np.ndarray
    mesh_receivers: np.ndarray
    mesh_edges: np.ndarray      # (E_mesh, 4) edge features
    g2m_senders: np.ndarray     # grid nodes
    g2m_receivers: np.ndarray   # mesh nodes
    g2m_edges: np.ndarray
    m2g_senders: np.ndarray     # mesh nodes
    m2g_receivers: np.ndarray   # grid nodes
    m2g_edges: np.ndarray
    layouts: dict               # `edge_layouts` of the three edge sets

    @property
    def sizes(self) -> dict:
        return {"n_grid": int(self.grid_nodes.shape[0]), "n_mesh": int(self.mesh_nodes.shape[0]),
                "n_mesh_edges": int(self.mesh_senders.shape[0]),
                "n_g2m": int(self.g2m_senders.shape[0]), "n_m2g": int(self.m2g_senders.shape[0])}

    def arrays(self) -> dict:
        """The structural entries of a GraphCast batch: node and edge
        features, each edge set's senders and receivers, and the layouts
        with which `repro.models.graphcast` sums over them with no scatter
        (`edge_layouts`, `repro.graph.ops.sorted_layout`).

        For each edge set and each of its two index arrays, the edges in
        ascending order of that index (as they are for the receivers; by
        ``<name>_send_order`` for the senders) are cut into tiles of
        `repro.graph.ops.SORTED_TILE` edges, ``*_tiles``
        ``(n_tiles, 1, SORTED_TILE)`` their node ids; the nodes into blocks
        of 128 rows; ``*_pairs`` ``(3, P)`` lists, block by block, the tiles
        that hold each block's edges. ``n_tiles`` and ``P`` are read from
        the graph: ``P`` is about the blocks plus the tiles that straddle two
        blocks. The sums are float32: the messages enter the MXU as exact
        bfloat16 parts, accumulated in float32."""
        out = {k: getattr(self, k) for k in (
            "grid_nodes", "mesh_nodes", "mesh_senders", "mesh_receivers", "mesh_edges",
            "g2m_senders", "g2m_receivers", "g2m_edges",
            "m2g_senders", "m2g_receivers", "m2g_edges")}
        return dict(out, **self.layouts)


def build_graph(resolution: float, splits: int, min_level: int,
                radius_fraction: float) -> GraphCastGraph:
    """The grid, the multimesh and the two bipartite graphs between them."""
    with _obs_trace.span("graphcast.build_graph"):
        meshes = mesh_hierarchy(splits)
        finest = meshes[-1]
        mesh_xyz = finest.vertices
        ms, mr = multimesh_edges(meshes, min_level)
        fs, fr = mesh_edges(finest.faces)
        longest = np.linalg.norm(mesh_xyz[fs] - mesh_xyz[fr], axis=-1).max()

        lat, lon = latlon_grid(resolution)
        glat = np.repeat(lat, lon.shape[0])
        glon = np.tile(lon, lat.shape[0])
        grid_xyz = latlon_to_xyz(glat, glon)
        gs, gr = radius_edges(grid_xyz, mesh_xyz, radius_fraction * longest)

        faces = finest.faces[containing_faces(grid_xyz, meshes)]
        m2g_s, m2g_r = _by_receiver(faces.reshape(-1), np.repeat(np.arange(grid_xyz.shape[0]), 3))

        mlat, mlon = _xyz_to_latlon(mesh_xyz)
        n_grid, n_mesh = grid_xyz.shape[0], mesh_xyz.shape[0]
        layouts = {**edge_layouts("mesh", ms, mr, n_mesh, n_mesh),
                   **edge_layouts("g2m", gs, gr, n_grid, n_mesh),
                   **edge_layouts("m2g", m2g_s, m2g_r, n_mesh, n_grid)}
        return GraphCastGraph(
            mesh_xyz=mesh_xyz,
            grid_nodes=node_features(glat, glon), mesh_nodes=node_features(mlat, mlon),
            mesh_senders=ms, mesh_receivers=mr,
            mesh_edges=edge_features(mesh_xyz[ms], mesh_xyz[mr], mlat[mr], mlon[mr]),
            g2m_senders=gs, g2m_receivers=gr,
            g2m_edges=edge_features(grid_xyz[gs], mesh_xyz[gr], mlat[gr], mlon[gr]),
            m2g_senders=m2g_s, m2g_receivers=m2g_r,
            m2g_edges=edge_features(mesh_xyz[m2g_s], grid_xyz[m2g_r], glat[m2g_r], glon[m2g_r]),
            layouts=layouts,
        )


@functools.lru_cache(maxsize=8)
def graph_sizes(resolution: float, splits: int, min_level: int,
                radius_fraction: float) -> tuple[tuple[str, int], ...]:
    """`GraphCastGraph.sizes` of a geometry, as sorted pairs (the build is
    seconds at 1°; abstract cells and FLOP counts ask for it repeatedly)."""
    return tuple(sorted(build_graph(resolution, splits, min_level, radius_fraction).sizes.items()))


@functools.lru_cache(maxsize=8)
def graph_shapes(resolution: float, splits: int, min_level: int,
                 radius_fraction: float) -> tuple[tuple[str, tuple[int, ...], str], ...]:
    """``(key, shape, dtype)`` of each entry of `GraphCastGraph.arrays` of a
    geometry, for abstract batches."""
    graph = build_graph(resolution, splits, min_level, radius_fraction)
    return tuple((k, v.shape, str(v.dtype)) for k, v in sorted(graph.arrays().items()))
