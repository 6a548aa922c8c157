"""RayDMatrix: the distributed data handle for train()/predict().

API-compatible re-implementation of ``xgboost_ray/matrix.py`` (RayDMatrix,
RayShardingMode, combine_data, central/distributed loaders, qid sorting),
re-targeted at the TPU runtime: shards are host numpy dicts keyed by actor
rank; the engine device_puts them onto the mesh and bins them there
(HBM-resident quantile-binned blocks replace xgboost's C++ DMatrix).

Central loading (driver loads everything, shards by row) and distributed
loading (each rank loads its own files/partitions) mirror
``matrix.py:431-487`` and ``matrix.py:614-693`` respectively; the sharding
index math and prediction re-assembly mirror ``matrix.py:1088-1157``.
"""

import glob
import os
import uuid
from enum import Enum
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np
import pandas as pd

from xgboost_ray_tpu.data_sources import DataSource, RayFileType, data_sources
from xgboost_ray_tpu.data_sources._distributed import (
    assign_partitions_to_actors,
    get_actor_rank_hosts,
)

Data = Union[str, List[str], np.ndarray, pd.DataFrame, pd.Series, Sequence[Any]]


class RayShardingMode(Enum):
    """How rows (or files, for distributed loading) map to actor ranks.

    Mirrors ``xgboost_ray/matrix.py:106-124``: INTERLEAVED strides rows over
    ranks, BATCH gives contiguous blocks, FIXED pins pre-assigned partitions.
    """

    INTERLEAVED = 1
    BATCH = 2
    FIXED = 3


def _batch_split_points(num_actors: int, n: int) -> np.ndarray:
    """Contiguous BATCH row boundaries (the reference's remainder
    semantics, ``matrix.py:1088-1110``): rank r owns
    ``[points[r], points[r+1])``. The ONE place the split math lives —
    consumed by ``_get_sharding_indices`` and the streamed .npy row
    windows, which must never diverge."""
    n_per_actor, extras = divmod(n, num_actors)
    sizes = [n_per_actor + 1] * extras + [n_per_actor] * (num_actors - extras)
    return np.concatenate([[0], np.cumsum(sizes)])


def _get_sharding_indices(
    sharding: RayShardingMode, rank: int, num_actors: int, n: int
) -> List[int]:
    """Row/file indices owned by ``rank`` (semantics of ``matrix.py:1088-1110``)."""
    if sharding == RayShardingMode.BATCH:
        points = _batch_split_points(num_actors, n)
        return list(range(points[rank], points[rank + 1]))
    if sharding == RayShardingMode.INTERLEAVED:
        return list(range(rank, n, num_actors))
    raise ValueError(
        f"Invalid value for `sharding` parameter: {sharding}. Pass a "
        f"RayShardingMode enum member, e.g. RayShardingMode.BATCH."
    )


def combine_data(sharding: RayShardingMode, data: Iterable) -> np.ndarray:
    """Re-assemble per-rank prediction shards into original row order
    (inverse of ``_get_sharding_indices``; semantics of ``matrix.py:1114-1157``)."""
    if sharding not in (RayShardingMode.BATCH, RayShardingMode.INTERLEAVED):
        raise ValueError(
            f"Invalid value for `sharding` parameter: {sharding}. Pass a "
            f"RayShardingMode enum member, e.g. RayShardingMode.BATCH."
        )
    parts = [np.asarray(d) for d in data if len(d)]
    if not parts:
        return np.array([])
    if sharding == RayShardingMode.BATCH:
        return np.concatenate(parts, axis=0)
    # INTERLEAVED: ranks may be off by one for uneven splits. Stacking on a
    # new axis 1 then flattening restores row order for ANY trailing shape
    # (scalars, softprob [K], SHAP [F+1] / [K,F+1], interactions
    # [F+1,F+1], leaf indices [T]).
    min_len = min(len(d) for d in parts)
    res = np.stack([d[:min_len] for d in parts], axis=1).reshape(
        (len(parts) * min_len,) + parts[0].shape[1:]
    )
    tails = [d[min_len:] for d in parts if len(d) > min_len]
    if tails:
        res = np.concatenate([res] + tails, axis=0)
    return res


def qid_sort_order(qid) -> Optional[np.ndarray]:
    """Stable order making query groups contiguous, or None if already sorted
    (``matrix.py:70-102`` semantics)."""
    order = np.argsort(np.asarray(qid), kind="stable")
    if np.all(order == np.arange(len(order))):
        return None
    return order


def ensure_sorted_by_qid(df: pd.DataFrame, qid) -> Tuple[pd.DataFrame, Any]:
    """Stable-sort rows so query groups are contiguous (``matrix.py:70-102``)."""
    order = qid_sort_order(qid)
    if order is None:
        return df, qid
    qid_sorted = qid.iloc[order] if isinstance(qid, pd.Series) else np.asarray(qid)[order]
    return df.iloc[order], qid_sorted


def translate_category_codes(
    col: np.ndarray, from_cats: Sequence[Any], to_cats: Sequence[Any]
) -> np.ndarray:
    """Re-map category codes encoded against ``from_cats`` onto ``to_cats``.

    Categories absent from ``to_cats`` become NaN (missing) — the same
    behavior xgboost shows for unseen categories at predict time.
    """
    mapping = np.full(len(from_cats), np.nan, np.float32)
    to_index = {v: i for i, v in enumerate(to_cats)}
    for i, v in enumerate(from_cats):
        if v in to_index:
            mapping[i] = to_index[v]
    out = np.full(col.shape, np.nan, np.float32)
    valid = ~np.isnan(col)
    out[valid] = mapping[col[valid].astype(np.int64)]
    return out


def translate_shard_categories(
    shard: Dict[str, Optional[np.ndarray]],
    from_cats: Optional[Dict[int, Sequence[Any]]],
    to_cats: Optional[Dict[int, Sequence[Any]]],
) -> Dict[str, Optional[np.ndarray]]:
    """Align an auto-encoded shard's category codes with a reference mapping
    (the training matrix's): frames with different category sets would
    otherwise assign different codes to the same value and be routed down
    wrong branches."""
    if not from_cats or not to_cats or from_cats == to_cats:
        # nothing auto-encoded on the source side -> codes are already in the
        # caller's mapping; avoid a pointless full copy
        return shard
    data = np.array(shard["data"], copy=True)
    for col, cats in (from_cats or {}).items():
        target = to_cats.get(col)
        if target is None or tuple(cats) == tuple(target):
            continue
        data[:, col] = translate_category_codes(data[:, col], cats, target)
    out = dict(shard)
    out["data"] = data
    return out


class _RayDMatrixLoader:
    """Shared loader logic: source resolution, dataframe splitting."""

    def __init__(
        self,
        data: Data,
        label: Optional[Data] = None,
        weight: Optional[Data] = None,
        feature_weights: Optional[Data] = None,
        base_margin: Optional[Data] = None,
        missing: Optional[float] = None,
        label_lower_bound: Optional[Data] = None,
        label_upper_bound: Optional[Data] = None,
        feature_names: Optional[List[str]] = None,
        feature_types: Optional[List[Any]] = None,
        qid: Optional[Data] = None,
        filetype: Optional[RayFileType] = None,
        ignore: Optional[List[str]] = None,
        enable_categorical: bool = False,
        **kwargs,
    ):
        self.data = data
        self.label = label
        self.weight = weight
        self.feature_weights = feature_weights
        self.base_margin = base_margin
        self.missing = missing
        self.label_lower_bound = label_lower_bound
        self.label_upper_bound = label_upper_bound
        self.feature_names = feature_names
        self.feature_types = feature_types
        self.qid = qid
        self.filetype = filetype
        self.ignore = ignore
        self.enable_categorical = enable_categorical
        self.kwargs = kwargs
        self.data_source: Optional[type] = None
        self.actor_shards: Optional[Dict[int, List[Any]]] = None
        self._resolved_feature_names: Optional[List[str]] = None
        self._resolved_feature_types: Optional[List[str]] = None
        # col index -> category values, recorded when columns auto-encode
        self._resolved_categories: Optional[Dict[int, tuple]] = None

    def get_data_source(self) -> type:
        if self.data_source is not None:
            return self.data_source
        filetype = self.filetype
        data = self.data
        for source in data_sources:
            if filetype is None and hasattr(source, "get_filetype"):
                filetype = source.get_filetype(data) or filetype
        for source in data_sources:
            if source.is_data_type(data, filetype):
                self.data_source = source
                self.filetype = filetype
                return source
        raise ValueError(
            f"Unable to infer data source for data of type {type(data)}. "
            f"Pass a supported data type (numpy array, pandas frame, "
            f"csv/parquet path(s), partition list) or specify `filetype`."
        )

    def _split_dataframe(self, df: pd.DataFrame) -> Dict[str, Optional[np.ndarray]]:
        """Extract label/weight/etc. columns; convert features to float32.

        Semantics of ``matrix.py:283-358``: string references select (and
        exclude) columns of the frame, array-likes attach externally.
        """
        source = self.get_data_source()
        exclude: List[str] = []

        def pick(ref):
            series, col = source.get_column(df, ref)
            if col is not None:
                exclude.append(col)
            return series

        label = pick(self.label)
        weight = pick(self.weight)
        base_margin = pick(self.base_margin)
        ll = pick(self.label_lower_bound)
        lu = pick(self.label_upper_bound)
        qid = pick(self.qid)

        x = df.drop(columns=[c for c in exclude if c in df.columns])
        if self.ignore:
            x = x.drop(columns=[c for c in self.ignore if c in x.columns])

        if qid is not None:
            order = qid_sort_order(qid)
            if order is not None:
                x = x.iloc[order]
                qid = np.asarray(qid)[order]
                label = None if label is None else np.asarray(label)[order]
                weight = None if weight is None else np.asarray(weight)[order]
                base_margin = None if base_margin is None else np.asarray(base_margin)[order]
                ll = None if ll is None else np.asarray(ll)[order]
                lu = None if lu is None else np.asarray(lu)[order]

        self._resolved_feature_names = self.feature_names or [str(c) for c in x.columns]

        # categorical columns -> integer codes ('c' in the feature-type map).
        # Encoding a column requires the global category set, so auto-encoding
        # is a central-loading feature; distributed shards must arrive
        # pre-encoded (pass feature_types=['c', ...] with numeric codes).
        cat_cols = [
            c
            for c in x.columns
            if isinstance(x[c].dtype, pd.CategoricalDtype)
            or not pd.api.types.is_numeric_dtype(x[c].dtype)
        ]
        ftypes = list(self.feature_types) if self.feature_types else None
        if cat_cols:
            if not self.enable_categorical:
                raise ValueError(
                    f"DataFrame has categorical/object columns {cat_cols}; "
                    f"pass enable_categorical=True (or encode them "
                    f"numerically) — mirroring xgboost.DMatrix semantics."
                )
            if isinstance(self, _DistributedRayDMatrixLoader):
                raise ValueError(
                    "categorical columns cannot be auto-encoded under "
                    "distributed loading (per-shard category sets would "
                    "disagree); encode to integer codes and pass "
                    "feature_types, or use central loading."
                )
            if ftypes is None:
                ftypes = [
                    "c" if c in cat_cols else "q" for c in x.columns
                ]
            x = x.copy()
            categories: Dict[int, tuple] = {}
            col_pos = {c: i for i, c in enumerate(x.columns)}
            for c in cat_cols:
                as_cat = x[c].astype("category")
                categories[col_pos[c]] = tuple(as_cat.cat.categories.tolist())
                codes = as_cat.cat.codes.astype(np.float32)
                x[c] = codes.where(codes >= 0, np.nan)  # -1 == missing
            self._resolved_categories = categories
        elif self.enable_categorical and ftypes is None:
            ftypes = ["q"] * len(x.columns)
        self._resolved_feature_types = ftypes

        feats = x.to_numpy(dtype=np.float32, copy=False)
        if self.missing is not None and not np.isnan(self.missing):
            feats = np.where(feats == np.float32(self.missing), np.nan, feats)

        def arr(v, dtype=np.float32):
            return None if v is None else np.asarray(v, dtype=dtype).ravel()

        return {
            "data": feats,
            "label": arr(label),
            "weight": arr(weight),
            "base_margin": arr(base_margin),
            "label_lower_bound": arr(ll),
            "label_upper_bound": arr(lu),
            "qid": None if qid is None else np.asarray(qid).ravel(),
        }


class _CentralRayDMatrixLoader(_RayDMatrixLoader):
    """Driver loads the full dataset once, then row-shards per rank
    (``matrix.py:431-487``)."""

    def load_fields(self) -> Dict[str, Optional[np.ndarray]]:
        """Load + split ONCE without per-rank copies (the streamed central
        path slices chunks out of these arrays lazily)."""
        source = self.get_data_source()
        df = source.load_data(self.data, ignore=self.ignore, **self.kwargs)
        df = source.update_feature_names(df, None)
        return self._split_dataframe(df)

    def load_data(self, num_actors: int, sharding: RayShardingMode):
        fields = self.load_fields()
        n = fields["data"].shape[0]
        if num_actors > n:
            raise RuntimeError(
                f"Trying to shard data for {num_actors} actors, but the "
                f"dataset has only {n} rows. Use fewer actors."
            )
        refs: Dict[int, Dict[str, Optional[np.ndarray]]] = {}
        for rank in range(num_actors):
            idx = _get_sharding_indices(sharding, rank, num_actors, n)
            refs[rank] = {
                k: (v[idx] if v is not None else None) for k, v in fields.items()
            }
        return refs, n


class _DistributedRayDMatrixLoader(_RayDMatrixLoader):
    """Each rank loads only its own files/partitions (``matrix.py:614-693``)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # with per-rank loading, external arrays cannot be aligned to shard
        # rows — only column-name references work (reference matrix.py:533-538)
        for field in ("label", "weight", "base_margin", "label_lower_bound",
                      "label_upper_bound", "qid"):
            val = getattr(self, field)
            if val is not None and not isinstance(val, str):
                raise ValueError(
                    f"Distributed data loading only works with column names "
                    f"for `{field}`, got {type(val)}. Pass the name of the "
                    f"column in your data files, or use central loading "
                    f"(`distributed=False`)."
                )

    def _expand(self) -> Any:
        data = self.data
        if isinstance(data, str) and os.path.isdir(data):
            files = sorted(
                glob.glob(os.path.join(data, "**", "*"), recursive=True)
            )
            files = [f for f in files if os.path.isfile(f)]
            return files
        if isinstance(data, str):
            hits = sorted(glob.glob(data))
            if len(hits) > 1:
                return hits
        return data

    def load_shard(self, rank: int, num_actors: int, sharding: RayShardingMode):
        source = self.get_data_source()
        data = self._expand()
        if self.actor_shards is not None:  # FIXED: pre-assigned partitions
            indices = self.actor_shards.get(rank, [])
            df = source.load_data(
                data, ignore=self.ignore, indices=indices, **self.kwargs
            )
        else:
            n_parts = source.get_n(data)
            if num_actors > n_parts:
                raise RuntimeError(
                    f"Trying to shard {n_parts} files/partitions across "
                    f"{num_actors} actors: use fewer actors or central loading."
                )
            indices = _get_sharding_indices(sharding, rank, num_actors, n_parts)
            df = source.load_data(
                data, ignore=self.ignore, indices=indices, **self.kwargs
            )
        df = source.update_feature_names(df, None)
        return self._split_dataframe(df)

    def assign_shards(self, num_actors: int):
        """FIXED sharding: locality-aware partition assignment
        (``matrix.py:595-612`` + ``_distributed.py:24-112``)."""
        data = self._expand()
        source = self.get_data_source()
        # distributed-frame sources (modin/dask/ray.data) provide their own
        # partition objects + locality assignment
        _, assignment = source.get_actor_shards(data, list(range(num_actors)))
        if assignment:
            self.actor_shards = assignment
            return
        n_parts = source.get_n(data)
        hosts = get_actor_rank_hosts(num_actors)
        host_to_parts = {"localhost": list(range(n_parts))}
        self.actor_shards = assign_partitions_to_actors(host_to_parts, hosts)


class RayDMatrix:
    """Distributed data handle (API of ``xgboost_ray/matrix.py:697-968``).

    Lazy by default: pass ``num_actors`` to load eagerly, or the ``train()``/
    ``predict()`` functions will trigger loading with their actor count.
    """

    def __init__(
        self,
        data: Data,
        label: Optional[Data] = None,
        weight: Optional[Data] = None,
        feature_weights: Optional[Data] = None,
        base_margin: Optional[Data] = None,
        missing: Optional[float] = None,
        label_lower_bound: Optional[Data] = None,
        label_upper_bound: Optional[Data] = None,
        feature_names: Optional[List[str]] = None,
        feature_types: Optional[List[Any]] = None,
        qid: Optional[Data] = None,
        enable_categorical: Optional[bool] = None,
        num_actors: Optional[int] = None,
        filetype: Optional[RayFileType] = None,
        ignore: Optional[List[str]] = None,
        distributed: Optional[bool] = None,
        sharding: RayShardingMode = RayShardingMode.INTERLEAVED,
        lazy: bool = False,
        stream: bool = False,
        chunk_rows: Optional[int] = None,
        budget_mb: Optional[float] = None,
        sketch_capacity: Optional[int] = None,
        **kwargs,
    ):
        # streamed ingestion mode (ROADMAP item 1): shards materialize as
        # chunked readers instead of raw arrays; the engine's two-pass
        # sketch->bin pipeline keeps peak host memory O(chunk + sketch).
        # RXGB_STREAM_* env knobs fill whatever isn't passed explicitly.
        self.streamed = bool(stream)
        self.stream_config = None
        if self.streamed:
            from xgboost_ray_tpu.stream.reader import StreamConfig

            self.stream_config = StreamConfig(
                chunk_rows=chunk_rows,
                budget_mb=budget_mb,
                sketch_capacity=sketch_capacity,
            )
        elif chunk_rows is not None or budget_mb is not None \
                or sketch_capacity is not None:
            raise ValueError(
                "chunk_rows/budget_mb/sketch_capacity require stream=True "
                "(or RayStreamingDMatrix)."
            )
        if kwargs.get("group", None) is not None:
            raise ValueError(
                "`group` parameter is not supported; use `qid` instead."
            )
        if qid is not None and weight is not None:
            raise NotImplementedError("per-group weight is not implemented.")
        kwargs.pop("group", None)

        self._uid = uuid.uuid4().int
        self.feature_names = feature_names
        self.feature_types = feature_types
        self.missing = missing
        self.num_actors = num_actors
        self.sharding = sharding

        if distributed is None:
            distributed = self._can_load_distributed(data)
        elif distributed and not self._can_load_distributed(data):
            raise ValueError(
                f"Distributed loading is not supported for data of type "
                f"{type(data)}; pass file paths or partition lists."
            )
        self.distributed = distributed

        loader_cls = _DistributedRayDMatrixLoader if distributed else _CentralRayDMatrixLoader
        self.loader = loader_cls(
            data=data,
            label=label,
            weight=weight,
            feature_weights=feature_weights,
            base_margin=base_margin,
            missing=missing,
            label_lower_bound=label_lower_bound,
            label_upper_bound=label_upper_bound,
            feature_names=feature_names,
            feature_types=feature_types,
            qid=qid,
            filetype=filetype,
            ignore=ignore,
            enable_categorical=bool(enable_categorical),
            **kwargs,
        )

        self.refs: Dict[int, Dict[str, Optional[np.ndarray]]] = {}
        self.n: Optional[int] = None
        self.loaded = False

        # distributed-frame sources pin partitions to ranks: FIXED sharding
        # is set automatically (reference matrix.py:106-124 docstring)
        if distributed:
            try:
                source = self.loader.get_data_source()
                if getattr(source, "__name__", "") in ("Modin", "Dask", "RayDataset"):
                    self.sharding = RayShardingMode.FIXED
            except ValueError:
                pass  # source resolution errors surface at load time

        if num_actors is not None and not lazy:
            self.load_data(num_actors)

    @property
    def feature_weights(self) -> Optional[np.ndarray]:
        """Per-feature sampling weights (length n_features), resolved to a
        float32 array; biases the engine's colsample_* draws (reference
        surface: xgboost_ray/matrix.py:283-358 -> DMatrix feature_weights)."""
        fw = getattr(self.loader, "feature_weights", None)
        if fw is None:
            return None
        return np.asarray(fw, dtype=np.float32).ravel()

    @staticmethod
    def _can_load_distributed(data: Data) -> bool:
        if isinstance(data, str):
            # a single CSV cannot be row-split across workers; a single
            # parquet can (row groups), directories/globs expand to files
            # (reference semantics: matrix.py:1036-1060)
            return data.endswith(".parquet") or os.path.isdir(data)
        if isinstance(data, (list, tuple)) and data and isinstance(data[0], str):
            return True
        if isinstance(data, (list, tuple)) and data:
            return True  # partition list
        if hasattr(data, "__partitioned__"):
            return True
        # distributed-frame sources (modin/dask/ray.data) own their partitions
        # (reference matrix.py:1036-1060 checks the same frame types)
        for source in data_sources:
            if getattr(source, "supports_distributed_loading", False) and source.is_data_type(data, None):
                return True
        return False

    def assert_enough_shards_for_actors(self, num_actors: int) -> None:
        """Distributed mode: fail fast when files/partitions < actors
        (``xgboost_ray/matrix.py:900-901`` / ``:576-592``)."""
        if not isinstance(self.loader, _DistributedRayDMatrixLoader):
            return
        data = self.loader._expand()
        source = self.loader.get_data_source()
        n_shards = source.get_n(data)
        if num_actors > n_shards:
            raise RuntimeError(
                f"Trying to shard data for {num_actors} actors, but it only "
                f"has {n_shards} files/partitions. Use fewer actors, "
                f"re-partition, or pass `distributed=False` for centralized "
                f"row sharding."
            )

    # -- loading -----------------------------------------------------------

    def load_data(self, num_actors: Optional[int] = None):
        if num_actors is not None:
            if self.num_actors is not None and self.num_actors != num_actors:
                raise ValueError(
                    f"The number of actors of a RayDMatrix cannot change once "
                    f"set ({self.num_actors} -> {num_actors})."
                )
            self.num_actors = num_actors
        if self.num_actors is None:
            raise ValueError("Pass `num_actors` to load a RayDMatrix.")
        if self.loaded:
            return
        if self.streamed:
            self._load_streamed()
            self.loaded = True
            return
        if isinstance(self.loader, _CentralRayDMatrixLoader):
            self.refs, self.n = self.loader.load_data(self.num_actors, self.sharding)
            self.loaded = True
        else:
            # distributed: shards materialize per rank in get_data
            self.loaded = True

    # -- streamed loading --------------------------------------------------

    @staticmethod
    def _is_npy(path) -> bool:
        return isinstance(path, str) and path.endswith(".npy")

    def _load_streamed(self) -> None:
        """Build the per-rank {"stream": ShardStream} refs.

        Three chunk sources: a .npy feature file (raw offset reads; BATCH
        row windows per rank), in-memory central data (lazy row slices of
        the once-loaded arrays — no per-rank copies), and file lists
        (per-rank CSV/Parquet chunk iteration, built lazily in get_data).
        """
        from xgboost_ray_tpu.stream.reader import (
            fields_shard_stream,
            npy_shard_stream,
        )

        if self._is_npy(self.loader.data):
            if self.sharding != RayShardingMode.BATCH:
                raise ValueError(
                    "streamed .npy ingestion reads contiguous row windows; "
                    "pass sharding=RayShardingMode.BATCH."
                )
            for field, val in (("label", self.loader.label),
                               ("weight", self.loader.weight)):
                if val is not None and not self._is_npy(val):
                    raise ValueError(
                        f"streamed .npy ingestion takes `{field}` as a "
                        f".npy path aligned row-for-row with the data file."
                    )
            # anything the npy reader cannot deliver must fail loudly, not
            # silently train without it (the no-silent-fallback invariant)
            for field in ("base_margin", "label_lower_bound",
                          "label_upper_bound", "qid"):
                if getattr(self.loader, field) is not None:
                    raise NotImplementedError(
                        f"streamed .npy ingestion supports label/weight "
                        f"side files only; `{field}` would be silently "
                        f"dropped. Use CSV/Parquet streaming (column "
                        f"references) or materialize the matrix."
                    )
            # ditto for the dataframe-split transforms the raw offset reads
            # bypass: a `missing` sentinel would be sketched/binned as real
            # feature values, and `ignore` has no column names to act on
            if self.loader.missing is not None and \
                    not np.isnan(self.loader.missing):
                raise NotImplementedError(
                    "streamed .npy ingestion does not apply a `missing` "
                    "sentinel (raw offset reads bypass the dataframe "
                    "split); encode missing values as NaN in the .npy "
                    "file, or use CSV/Parquet streaming."
                )
            if self.loader.ignore:
                raise NotImplementedError(
                    "streamed .npy ingestion cannot honor `ignore`: a "
                    ".npy matrix has no column names. Drop the columns "
                    "from the file, or use CSV/Parquet streaming."
                )
            probe = npy_shard_stream(self.loader.data, config=self.stream_config)
            n = probe.n_rows
            if self.num_actors > n:
                raise RuntimeError(
                    f"Trying to shard data for {self.num_actors} actors, "
                    f"but the dataset has only {n} rows. Use fewer actors."
                )
            points = _batch_split_points(self.num_actors, n)
            for rank in range(self.num_actors):
                self.refs[rank] = {"stream": npy_shard_stream(
                    self.loader.data,
                    label_path=self.loader.label,
                    weight_path=self.loader.weight,
                    config=self.stream_config,
                    row_range=(int(points[rank]), int(points[rank + 1])),
                )}
            self.n = n
            return
        if isinstance(self.loader, _CentralRayDMatrixLoader):
            fields = self.loader.load_fields()
            n = fields["data"].shape[0]
            if self.num_actors > n:
                raise RuntimeError(
                    f"Trying to shard data for {self.num_actors} actors, "
                    f"but the dataset has only {n} rows. Use fewer actors."
                )
            for rank in range(self.num_actors):
                idx = np.asarray(_get_sharding_indices(
                    self.sharding, rank, self.num_actors, n
                ))
                self.refs[rank] = {"stream": fields_shard_stream(
                    fields, idx, config=self.stream_config,
                    source_token=("central", self._uid, rank),
                )}
            self.n = n
            return
        # distributed file lists: per-rank streams build lazily in get_data

    def _streamed_file_shard(self, rank: int) -> Dict[str, Any]:
        from xgboost_ray_tpu.stream.reader import file_shard_stream

        loader = self.loader
        data = loader._expand()
        source = loader.get_data_source()
        if loader.actor_shards is not None:
            indices = loader.actor_shards.get(rank, [])
        else:
            n_parts = source.get_n(data)
            if self.num_actors > n_parts:
                raise RuntimeError(
                    f"Trying to shard {n_parts} files/partitions across "
                    f"{self.num_actors} actors: use fewer actors or central "
                    f"loading."
                )
            indices = _get_sharding_indices(
                self.sharding, rank, self.num_actors, n_parts
            )
        files = [data[i] for i in indices] if isinstance(data, (list, tuple)) \
            else ([data] if indices else [])
        if not files or not all(isinstance(f, str) for f in files):
            raise NotImplementedError(
                "streamed distributed loading needs file paths (CSV or "
                "Parquet); partition/frame sources must be materialized."
            )
        ftype = {RayFileType.CSV: "csv", RayFileType.PARQUET: "parquet"}.get(
            loader.filetype
        )
        if ftype is None:
            raise NotImplementedError(
                f"streamed ingestion supports CSV/Parquet/.npy sources; got "
                f"filetype {loader.filetype!r}."
            )

        def split_fn(df):
            df = source.update_feature_names(df, None)
            return loader._split_dataframe(df)

        return {"stream": file_shard_stream(
            files, split_fn, ftype, config=self.stream_config,
            read_kwargs=loader.kwargs,
        )}

    def get_data(
        self, rank: int, num_actors: Optional[int] = None
    ) -> Dict[str, Optional[np.ndarray]]:
        self.load_data(num_actors)
        if rank not in self.refs:
            if not isinstance(self.loader, _DistributedRayDMatrixLoader):
                raise KeyError(f"No shard for rank {rank}")
            if self.streamed:
                self.refs[rank] = self._streamed_file_shard(rank)
            else:
                self.refs[rank] = self.loader.load_shard(
                    rank, self.num_actors, self.sharding
                )
        return self.refs[rank]

    def unload_data(self):
        self.refs = {}
        self.loaded = False

    def assign_shards_to_actors(self, actors: Sequence[Any]) -> bool:
        """FIXED-mode locality assignment before training (``matrix.py:595-612``)."""
        if self.sharding != RayShardingMode.FIXED:
            return False
        if not isinstance(self.loader, _DistributedRayDMatrixLoader):
            return False
        if self.loader.actor_shards is None:
            self.loader.assign_shards(self.num_actors or len(actors))
        return True

    # -- introspection -----------------------------------------------------

    def get_shard_sizes(self) -> Dict[int, int]:
        def size(s):
            if s.get("stream") is not None:
                return s["stream"].n_rows
            return s["data"].shape[0] if s.get("data") is not None else 0

        return {r: size(s) for r, s in self.refs.items()}

    def get_shard_bytes(self) -> Dict[int, int]:
        """Host bytes of each materialised shard's arrays, by rank (a
        streamed shard holds no rows on the host: 0)."""
        return {
            r: sum(int(v.nbytes) for v in s.values()
                   if isinstance(v, np.ndarray))
            for r, s in self.refs.items()
        }

    @property
    def resolved_feature_names(self) -> Optional[List[str]]:
        return self.feature_names or self.loader._resolved_feature_names

    @property
    def resolved_feature_types(self) -> Optional[List[str]]:
        """Per-feature type map ('c' categorical / 'q' numeric), from the
        user's feature_types or detected category-dtype columns."""
        if self.feature_types:
            return list(self.feature_types)
        return self.loader._resolved_feature_types

    @property
    def resolved_categories(self) -> Optional[Dict[int, tuple]]:
        """col index -> category values for auto-encoded columns (used to
        align eval/predict frames with the training encoding)."""
        return self.loader._resolved_categories

    @property
    def has_label(self) -> bool:
        return self.loader.label is not None

    def __hash__(self):
        return self._uid

    def __eq__(self, other):
        return isinstance(other, RayDMatrix) and self._uid == other._uid


class RayStreamingDMatrix(RayDMatrix):
    """Out-of-core ingestion mode: shards are chunked readers, not arrays.

    Equivalent to ``RayDMatrix(..., stream=True)``. Training never
    materializes the raw [N, F] float32 shard: a deterministic mergeable
    quantile sketch streams over chunks (pass 1), global cuts merge on the
    mesh through the materialized sketch program's collective shape, and
    each chunk bins straight into the per-actor ``bin_dtype`` buffer with
    double-buffered host→device upload (pass 2). Peak host memory is
    O(chunk + sketch). Loads that fit in one chunk take the EXACT
    materialized path (bitwise-identical cuts, bins, and trained forest).

    Knobs (env fallbacks in parentheses): ``chunk_rows``
    (``RXGB_STREAM_CHUNK_ROWS``), ``budget_mb`` (``RXGB_STREAM_BUDGET_MB``;
    also derives chunk_rows when unset and validates the configured peak),
    ``sketch_capacity`` (``RXGB_STREAM_SKETCH_CAP``). See README
    "Streaming ingestion" for the memory model and composition matrix.
    """

    def __init__(self, *args, **kwargs):
        kwargs["stream"] = True
        super().__init__(*args, **kwargs)


class RayQuantileDMatrix(RayDMatrix):
    """Alias of RayDMatrix: all tpu_hist matrices are quantile-binned on
    device (the reference's distinction, ``matrix.py:971-975``, is a CUDA
    memory optimization that is the default here)."""


class RayDeviceQuantileDMatrix(RayDMatrix):
    """Accepted for API compatibility (``matrix.py:977-1033``); on TPU every
    matrix is already an HBM-resident quantile-binned block, so this behaves
    exactly like RayDMatrix."""

    def __init__(self, *args, max_bin: Optional[int] = None, **kwargs):
        self.max_bin = max_bin
        super().__init__(*args, **kwargs)
