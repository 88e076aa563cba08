"""2D block-cyclic layouts for distributed posit matrices (ScaLAPACK
descriptor, mesh-native).

A posit matrix is int32 words, so a distributed posit matrix is an int32
plane sharded over a P x Q ("row", "col") process grid
(``launch.mesh.make_grid_mesh``).  Global block (bi, bj) — ``nb x nb``
posit words — is owned by device (bi mod P, bj mod Q) and stored at local
block (bi // P, bj // Q):

        global blocks                device (r, c) local tiles
      bj:  0    1    2    3            holds bi ≡ r (mod P),
    bi 0  0,0  0,1  0,0  0,1                 bj ≡ c (mod Q)
       1  1,0  1,1  1,0  1,1        e.g. P=Q=2, device (0,1):
       2  0,0  0,1  0,0  0,1             blocks (0,1) (0,3)
       3  1,0  1,1  1,0  1,1                    (2,1) (2,3)

Cyclic assignment keeps every device busy through a right-looking
factorization: as the trailing matrix shrinks, surviving blocks stay
spread over the whole grid instead of draining to one corner (the reason
ScaLAPACK block-cyclic exists).

**Representation.**  The distributed value is ONE jax.Array of shape
(P * lm, Q * ln) — device (r, c)'s (lm, ln) local tile sits at rows
[r*lm, (r+1)*lm) — sharded contiguously by ``PartitionSpec("row",
"col")``.  That makes the dist array a row/column *permutation* of the
zero-padded global matrix, so scatter/gather are pure index math
(``scatter_array`` / ``gather_array``), identical on host numpy and
traced values.  Padding blocks hold posit word 0 (value 0); by
construction they are the HIGHEST-indexed global blocks, so gather is a
plain slice after unpermuting.

Device-side helpers (used inside shard_map, where the device coordinate
is a traced ``axis_index``): ``local_gidx`` (global index of every local
row/col), ``unshuffle`` (axis-gathered tiles -> global order), and
``select_block_col`` (masked read of one global block column).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_grid_mesh, replicated

__all__ = ["BlockCyclic", "DistMatrix", "distribute", "scatter_array",
           "gather_array", "local_gidx", "unshuffle", "select_block_col",
           "grid_coords", "make_grid_mesh"]


@dataclasses.dataclass(frozen=True)
class BlockCyclic:
    """Layout descriptor: (m, n) global posit matrix, nb x nb blocks,
    P x Q grid.  Hashable — usable as a jit static argument."""
    m: int
    n: int
    nb: int
    p: int
    q: int

    @property
    def mb(self) -> int:                     # global block rows
        return -(-self.m // self.nb)

    @property
    def nbk(self) -> int:                    # global block cols
        return -(-self.n // self.nb)

    @property
    def lmb(self) -> int:                    # local block rows per device
        return -(-self.mb // self.p)

    @property
    def lnb(self) -> int:                    # local block cols per device
        return -(-self.nbk // self.q)

    @property
    def lm(self) -> int:                     # local rows per device
        return self.lmb * self.nb

    @property
    def ln(self) -> int:                     # local cols per device
        return self.lnb * self.nb

    def block_owner(self, bi: int, bj: int) -> tuple[int, int]:
        return bi % self.p, bj % self.q

    def col_block_home(self, j: int) -> tuple[int, int, int]:
        """Global column j -> (owner grid column, local block col index,
        offset within the local tile).  Static math for panel schedules."""
        bj = j // self.nb
        return bj % self.q, bj // self.q, (bj // self.q) * self.nb + j % self.nb


def _perm(g: int, blocks: int, lb: int):
    """Dist-order block index list: position (grid coord r, local t) holds
    global block r + g*t... i.e. entry k = (k // lb) + g * (k % lb)."""
    return [(k // lb) + g * (k % lb) for k in range(g * lb)]


def scatter_array(x, lay: BlockCyclic):
    """Replicated (m, n) posit words -> (P*lm, Q*ln) dist array (pure
    index permutation + zero padding; jnp, so it traces)."""
    x = jnp.asarray(x, jnp.int32)
    assert x.shape == (lay.m, lay.n), (x.shape, lay)
    pad_r, pad_c = lay.p * lay.lm - lay.m, lay.q * lay.ln - lay.n
    x = jnp.pad(x, ((0, pad_r), (0, pad_c)))
    t = x.reshape(lay.p * lay.lmb, lay.nb, lay.q * lay.lnb, lay.nb)
    bi = jnp.asarray(_perm(lay.p, lay.mb, lay.lmb))
    bj = jnp.asarray(_perm(lay.q, lay.nbk, lay.lnb))
    return t[bi][:, :, bj].reshape(lay.p * lay.lm, lay.q * lay.ln)


def gather_array(d, lay: BlockCyclic):
    """(P*lm, Q*ln) dist array -> replicated (m, n) posit words (inverse
    of ``scatter_array``)."""
    d = jnp.asarray(replicated(d))
    t = d.reshape(lay.p, lay.lmb, lay.nb, lay.q, lay.lnb, lay.nb)
    # dist block (r, t) holds global block r + P*t: ascending global order
    # is (t outer, r inner); padding blocks land at the end of each axis.
    g = t.transpose(1, 0, 2, 4, 3, 5).reshape(lay.p * lay.lm,
                                              lay.q * lay.ln)
    return g[:lay.m, :lay.n]


@dataclasses.dataclass
class DistMatrix:
    """A block-cyclic distributed posit matrix: the sharded int32 plane
    plus its layout and mesh.  ``data`` rows/cols are in dist (device-
    major) order — use ``gather()`` for the global-order matrix."""
    data: jax.Array
    layout: BlockCyclic
    mesh: jax.sharding.Mesh

    @property
    def shape(self):
        return (self.layout.m, self.layout.n)

    @property
    def spec(self):
        return jax.sharding.PartitionSpec("row", "col")

    def gather(self) -> jax.Array:
        return gather_array(self.data, self.layout)

    def with_data(self, data: jax.Array) -> "DistMatrix":
        return DistMatrix(data=data, layout=self.layout, mesh=self.mesh)


def distribute(x, mesh: jax.sharding.Mesh, nb: int = 32) -> DistMatrix:
    """Scatter a replicated (m, n) posit-word matrix onto the mesh."""
    p, q = mesh.shape["row"], mesh.shape["col"]
    x = jnp.asarray(x, jnp.int32)
    lay = BlockCyclic(m=x.shape[0], n=x.shape[1], nb=nb, p=p, q=q)
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("row", "col"))
    data = jax.device_put(scatter_array(x, lay), sharding)
    return DistMatrix(data=data, layout=lay, mesh=mesh)


# --------------------------------------------------------------------------
# device-side index math (inside shard_map; grid coordinate is traced)
# --------------------------------------------------------------------------

def local_gidx(lay: BlockCyclic, axis: int, coord):
    """Global row (axis=0) / column (axis=1) index of every local row/col
    on the device at traced grid coordinate ``coord``: local position
    t*nb + u maps to global (coord + g*t)*nb + u.  Padding rows/cols map
    past m/n — callers mask with ``< lay.m`` / ``< lay.n``."""
    g, lb = ((lay.p, lay.lmb) if axis == 0 else (lay.q, lay.lnb))
    t = jnp.arange(lb, dtype=jnp.int32)
    u = jnp.arange(lay.nb, dtype=jnp.int32)
    return ((coord + g * t[:, None]) * lay.nb + u[None, :]).reshape(-1)


def unshuffle(gathered: jax.Array, g: int, nb: int) -> jax.Array:
    """(g, lb*nb, ...) axis-0 ``all_gather`` of local tiles -> (g*lb*nb,
    ...) rows in GLOBAL order (gathered[r', t] holds global block
    r' + g*t, so ascending order is t-major)."""
    lb = gathered.shape[1] // nb
    t = gathered.reshape((g, lb, nb) + gathered.shape[2:])
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape((g * lb * nb,) + gathered.shape[2:])


def select_block_col(a_loc: jax.Array, lay: BlockCyclic, coord, j: int,
                     w: int) -> jax.Array:
    """Masked read of global columns [j, j+w) from a local tile: the
    owner grid column returns its (lm, w) slice, everyone else zeros —
    so a psum over "col" broadcasts the panel to the whole grid row.
    ``j`` is static (block schedule); ``coord`` is the traced grid
    column.  Requires the panel not to straddle a block boundary
    (j % nb + w <= nb, the LAPACK panel shape)."""
    c_star, _, off = lay.col_block_home(j)
    assert j % lay.nb + w <= lay.nb, (j, w, lay.nb)
    sl = jax.lax.slice_in_dim(a_loc, off, off + w, axis=1)
    return jnp.where(jnp.asarray(coord == c_star), sl, 0)


def grid_coords():
    """Traced (row, col) coordinate of the executing device."""
    return jax.lax.axis_index("row"), jax.lax.axis_index("col")
