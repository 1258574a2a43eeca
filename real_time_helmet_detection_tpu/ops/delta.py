"""Per-tile frame-delta summary + tile crop/stitch helpers (ISSUE 17).

The reference's end product is a C++ video loop that runs the FULL model
on every frame (ref README.md:76 — webcam/RTSP, one traced forward per
frame); the reference has no analogue of change detection. Surveillance
frames are overwhelmingly static, so the streaming plane
(serving/streams.py) pays only for what changed: this module supplies
the in-jit change signal and the host-side tile geometry it gates.

Design (all of it the repo's standing discipline):

* **Fixed tile grid, fixed shapes.** A frame is a `grid x grid` array
  of equal tiles whose size matches the tile model's input; the summary
  is ONE `(T,)` float32 leaf — masks decide downstream, never boolean
  filtering, so the jitted program never sees a dynamic shape.
* **uint8 in, one tiny program.** `tile_delta_summary` casts to f32
  INSIDE the jit (a uint8 subtract would wrap) and reduces |cur - prev|
  per tile with one `reduce_window` (window == stride == tile dims, the
  `peak_mask` idiom) — light on the host<->device link exactly like
  `decode.confidence_summary`: uint8 ships H2D, one small f32 block
  comes back.
* **Stitching is arithmetic, not model code.** Per-tile Detections ride
  back in tile-pixel coordinates; `stitch_detections` offsets boxes by
  the tile origin and concatenates the fixed-shape blocks, so a frame
  answer is always `(T * topk,)` rows with the valid mask intact.
"""

from functools import partial
from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .decode import Detections

# default tile grid (G x G tiles per frame); the streaming config's
# stream_tile_grid overrides it per session
TILE_GRID_DEFAULT = 2


def tile_shape(frame_shape: Tuple[int, ...], grid: int) -> Tuple[int, int]:
    """(tile_h, tile_w) for a (H, W, C) frame cut into a grid x grid
    tiling; raises unless the frame divides evenly (fixed shapes are the
    law — a ragged edge tile would be a dynamic shape under jit)."""
    h, w = int(frame_shape[0]), int(frame_shape[1])
    if grid < 1 or h % grid or w % grid:
        raise ValueError(
            "frame %dx%d does not divide into a %dx%d tile grid"
            % (h, w, grid, grid))
    return h // grid, w // grid


def tile_origins(frame_shape: Tuple[int, ...],
                 grid: int) -> List[Tuple[int, int]]:
    """Row-major (y0, x0) origins of the grid's T = grid*grid tiles —
    the ONE ordering every consumer (summary leaf, crop, stitch, cache)
    shares."""
    th, tw = tile_shape(frame_shape, grid)
    return [(gy * th, gx * tw)
            for gy in range(grid) for gx in range(grid)]


@partial(jax.jit, static_argnames=("grid",))
def tile_delta_summary(prev: jax.Array, cur: jax.Array,
                       grid: int = TILE_GRID_DEFAULT) -> jax.Array:
    """Mean absolute per-pixel change per tile: (H, W, C) uint8 pair ->
    (T,) float32 in [0, 255], row-major over the grid (tile_origins
    order). The whole program is one cast + one reduce_window — small
    enough that its dispatch rides the frame's existing H2D."""
    h, w, c = prev.shape
    th, tw = h // grid, w // grid
    diff = jnp.abs(cur.astype(jnp.float32) - prev.astype(jnp.float32))
    pooled = jax.lax.reduce_window(
        diff, 0.0, jax.lax.add,
        window_dimensions=(th, tw, c),
        window_strides=(th, tw, c),
        padding=((0, 0), (0, 0), (0, 0)))
    return (pooled / float(th * tw * c)).reshape(-1)


def make_delta_fn(grid: int = TILE_GRID_DEFAULT):
    """The session's summary program: (prev, cur) uint8 -> (T,) f32.
    The grid is baked static so every call traces the one program."""
    return partial(tile_delta_summary, grid=grid)


def crop_tile(frame: np.ndarray, y0: int, x0: int, th: int,
              tw: int) -> np.ndarray:
    """Fixed-shape host-side tile view (the session crops BEFORE submit,
    so the engine only ever sees the one tile shape)."""
    return frame[y0:y0 + th, x0:x0 + tw]


def offset_detections(det: Detections, y0: int, x0: int) -> Detections:
    """Shift a tile's detections into frame coordinates (boxes are
    x1,y1,x2,y2 in tile pixels — decode.decode_heatmap's layout). Pure
    numpy on the host; invalid rows shift too (harmless — the mask is
    the truth)."""
    boxes = np.asarray(det.boxes) + np.array(
        [x0, y0, x0, y0], dtype=np.float32)
    return Detections(boxes=boxes, classes=np.asarray(det.classes),
                      scores=np.asarray(det.scores),
                      valid=np.asarray(det.valid))


def stitch_detections(tile_dets: List[Detections],
                      origins: List[Tuple[int, int]]) -> Detections:
    """Concatenate per-tile fixed-shape blocks (in tile_origins order)
    into one frame-level Detections of T*topk rows — shape depends only
    on the grid and topk, never on what changed."""
    if len(tile_dets) != len(origins):
        raise ValueError("got %d tile results for %d tiles"
                         % (len(tile_dets), len(origins)))
    shifted = [offset_detections(d, y0, x0)
               for d, (y0, x0) in zip(tile_dets, origins)]
    return Detections(
        boxes=np.concatenate([d.boxes for d in shifted], axis=0),
        classes=np.concatenate([d.classes for d in shifted], axis=0),
        scores=np.concatenate([d.scores for d in shifted], axis=0),
        valid=np.concatenate([d.valid for d in shifted], axis=0))
