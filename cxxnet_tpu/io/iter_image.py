"""Image iterators: imgbin / imgbinx page readers, plain img iterator, and
the augmentation adapters.

Reference mapping:
* ImagePageIterator      <- ThreadImagePageIterator/X
  (src/io/iter_thread_imbin-inl.hpp:16, iter_thread_imbin_x-inl.hpp:18):
  BinaryPage packs of jpeg records + .lst label files; multi-part lists via
  image_conf_prefix/image_conf_ids; distributed file sharding by
  dist_num_worker/dist_worker_rank (env PS_RANK).
* ImageIterator          <- src/io/iter_img-inl.hpp:16 (per-file loading)
* GeometricAugmenter     <- src/io/image_augmenter-inl.hpp:13 (one cv2
  warpAffine combining rotation/shear/scale/aspect, then crop)
* AugmentIterator        <- src/io/iter_augment_proc-inl.hpp:21 (crop/mirror/
  mean-subtract with on-the-fly mean-image creation + caching, divideby,
  random contrast/illumination)

Images are decoded to float32 RGB (c, h, w) in [0, 255] like the reference
(iter_thread_imbin-inl.hpp:125-143); `divideby`/`scale` rescales afterward.
Decode uses cv2 (the reference's decoder); jpeg bytes are produced by
tools/im2bin.py.
"""

from __future__ import annotations

import math
import os
import sys
from typing import List, Optional

import numpy as np

from ..utils import telemetry
from ..utils.binary_page import BinaryPage, KPAGE_INTS
from .data import DataBatch, DataInst, IIterator
from .batch import BatchAdaptIterator


class RecordDecodeError(ValueError):
    """A single record's bytes do not decode to an image (corrupt jpeg,
    torn record, or a decode worker that had to be presumed dead). The
    page iterator skips + quarantines such records (``skip_corrupt=1``)
    instead of crashing the run."""


class PackReadError(RuntimeError):
    """The .bin pack ended or went unreadable before the .lst did —
    a truncated or corrupt pack file. Record/label alignment past this
    point is unrecoverable, so the epoch ends early (counted, warned,
    never a crash) rather than serving mislabeled images."""


def _decode_rgb_chw(buf: bytes) -> np.ndarray:
    # native path first: libjpeg decode + float CHW conversion in C++,
    # entirely off-GIL (src/core/jpeg_decode.cc) — this is what lets the
    # imgbinx decode thread pool scale
    with telemetry.span("io.decode"):
        from ..utils import native
        out = native.decode_jpeg_chw(buf)
        if out is not None:
            return out
        import cv2
        arr = np.frombuffer(buf, dtype=np.uint8)
        bgr = cv2.imdecode(arr, cv2.IMREAD_COLOR)
        if bgr is None:
            raise RecordDecodeError(
                "undecodable image record (%d bytes)" % len(buf))
        rgb = bgr[:, :, ::-1]
        return np.ascontiguousarray(
            rgb.transpose(2, 0, 1).astype(np.float32))


class _ListReader:
    """Reads .lst files: lines of ``index label[ label..] filename``."""

    def __init__(self, paths: List[str], label_width: int):
        self.paths = paths
        self.label_width = label_width
        self.reset()

    def reset(self):
        self.idx = 0
        self.f = open(self.paths[0])

    def close(self):
        if self.f is not None:
            self.f.close()
            self.f = None

    def next_record(self):
        while True:
            line = self.f.readline()
            if line.strip():
                toks = line.split()
                index = int(toks[0])
                label = np.asarray(
                    [float(x) for x in toks[1:1 + self.label_width]],
                    np.float32)
                fname = toks[1 + self.label_width] \
                    if len(toks) > 1 + self.label_width else ""
                return index, label, fname
            if not line:
                self.idx += 1
                if self.idx >= len(self.paths):
                    return None
                self.f.close()
                self.f = open(self.paths[self.idx])


class ImagePageIterator(IIterator):
    """imgbin/imgbinx: jpeg records from BinaryPage packs + .lst labels."""

    def __init__(self):
        self.silent = 0
        self.label_width = 1
        self.path_imglst: List[str] = []
        self.path_imgbin: List[str] = []
        self.img_conf_prefix = ""
        self.img_conf_ids = ""
        self.dist_num_worker = 0
        self.dist_worker_rank = 0
        self.page_ints = KPAGE_INTS
        self.lst: Optional[_ListReader] = None
        self.native_reader = None
        self.fbin = None
        # decode pipeline (the reference imgbinx two-stage ThreadBuffer,
        # iter_thread_imbin_x-inl.hpp): decode_thread workers decode jpegs
        # ahead of the consumer (cv2.imdecode releases the GIL), depth
        # buffer_size records. decode_thread=1 = synchronous decode (imgbin)
        self.decode_thread = 1
        self.buffer_size = 64
        self._pool = None
        self._pending = None
        self._lst_done = False
        # data-pipeline fault tolerance (doc/robustness.md): with
        # skip_corrupt=1 (default) a corrupt/truncated record is skipped,
        # counted (io.corrupt_records) and quarantined by instance index —
        # later epochs drop it before decode; a truncated pack ends the
        # epoch early instead of crashing. decode_timeout>0 bounds one
        # record's decode: a worker wedged past it is presumed dead, the
        # pool is rebuilt (pending decodes resubmitted) and the record is
        # quarantined.
        self.skip_corrupt = 1
        self.decode_timeout = 0.0
        self._quarantined = set()
        self._corrupt_seen = 0
        # shuffle=1 (reference iter_thread_imbin_x-inl.hpp:161-195,253-286):
        # part-file order is re-permuted every epoch, and instances are
        # shuffled within a seeded sliding window (the TPU-first analog of
        # the reference's within-page inst_order shuffle — same locality,
        # but independent of the physical page size and identical across
        # the native/Python readers). seed_data seeds the stream; the
        # window advances across epochs so every epoch draws a new order.
        self.shuffle = 0
        self.seed_data = 0
        self.shuffle_window = 1024
        self._rnd = None
        self._window: List = []
        self._part_order: List[int] = []

    def set_param(self, name, val):
        if name == "image_list":
            self.path_imglst.append(val)
        if name == "image_bin":
            self.path_imgbin.append(val)
        if name == "image_conf_prefix":
            self.img_conf_prefix = val
        if name == "image_conf_ids":
            self.img_conf_ids = val
        if name == "dist_num_worker":
            self.dist_num_worker = int(val)
        if name == "dist_worker_rank":
            self.dist_worker_rank = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "label_width":
            self.label_width = int(val)
        if name == "page_size":
            self.page_ints = int(val)
        if name == "decode_thread":
            self.decode_thread = int(val)
        if name == "buffer_size":
            self.buffer_size = int(val)
        if name == "shuffle":
            self.shuffle = int(val)
        if name == "seed_data":
            self.seed_data = int(val)
        if name == "shuffle_window":
            self.shuffle_window = int(val)
            assert self.shuffle_window >= 1, \
                "shuffle_window must be >= 1 (1 = stream order)"
        if name == "skip_corrupt":
            self.skip_corrupt = int(val)
        if name == "decode_timeout":
            self.decode_timeout = float(val)

    def _parse_image_conf(self):
        """Multi-part list + distributed sharding
        (reference ParseImageConf, iter_thread_imbin-inl.hpp:189-220)."""
        ps_rank = os.environ.get("PS_RANK")
        if ps_rank is not None:
            self.dist_worker_rank = int(ps_rank)
        if not self.img_conf_prefix:
            return
        assert not self.path_imglst and not self.path_imgbin, \
            "you can either set image_conf_prefix or image_bin/image_list"
        lb, ub = (int(x) for x in self.img_conf_ids.split("-"))
        n = ub + 1 - lb
        if self.dist_num_worker > 1:
            step = (n + self.dist_num_worker - 1) // self.dist_num_worker
            begin = min(self.dist_worker_rank * step, n) + lb
            end = min((self.dist_worker_rank + 1) * step, n) + lb
            lb, ub = begin, end - 1
            assert lb <= ub, ("ThreadImagePageIterator: too many workers "
                              "such that idlist cannot be divided between them")
        for i in range(lb, ub + 1):
            tmp = self.img_conf_prefix % i
            self.path_imglst.append(tmp + ".lst")
            self.path_imgbin.append(tmp + ".bin")

    def init(self):
        self._parse_image_conf()
        assert len(self.path_imgbin) == len(self.path_imglst), \
            "List/Bin number not consist"
        # kRandMagic = 121, mirroring the reference's sampler seed
        self._rnd = np.random.RandomState(self.seed_data + 121)
        self._part_order = list(range(len(self.path_imgbin)))
        # the sliding-window shuffle draws from _rnd on every instance, so
        # epoch k's order depends on all prior epochs' RNG state — a fresh
        # process cannot replay it; mid-round checkpoint resume is then
        # approximate (doc/robustness.md)
        self.stable_epoch_order = not self.shuffle
        self.before_first()
        if self.silent == 0:
            # which reader serves the pages is not the caller's choice
            # (utils/native.py falls back when the .so is absent): say it
            print("ImagePageIterator: image_list=%s, bin=%s, page_reader=%s"
                  % (",".join(self.path_imglst), ",".join(self.path_imgbin),
                     "native" if self.native_reader is not None
                     else "python"))

    def _epoch_paths(self):
        if self.shuffle and len(self._part_order) > 1:
            self._rnd.shuffle(self._part_order)
        return ([self.path_imglst[i] for i in self._part_order],
                [self.path_imgbin[i] for i in self._part_order])

    def before_first(self):
        lst_paths, bin_paths = self._epoch_paths()
        if self.lst is not None:
            self.lst.close()
        self.lst = _ListReader(lst_paths, self.label_width)
        reordered = self.shuffle and len(self._part_order) > 1
        if self.native_reader is not None and reordered:
            # per-epoch part order changed: rebuild the native read-ahead
            # chain over the permuted file list
            self.native_reader.close()
            self.native_reader = None
        if self.native_reader is None:
            from ..utils import native
            if native.load() is not None:
                try:
                    self.native_reader = native.NativePageReader(
                        bin_paths, self.page_ints)
                except (IOError, RuntimeError):
                    self.native_reader = None
        else:
            self.native_reader.before_first()
        self._epoch_bin_paths = bin_paths
        self.bin_idx = 0
        self.page = None
        self.ptop = 0
        from collections import deque
        self._pending = deque()
        self._lst_done = False
        self._window = []
        if getattr(self, "fbin", None) is not None:
            self.fbin.close()
            self.fbin = None
        if self.native_reader is None:
            self.fbin = open(bin_paths[0], "rb")

    def _next_buffer(self) -> bytes:
        # native path: C++ read-ahead thread parses pages off-GIL
        # (src/core/binary_page.cc PageReader)
        if self.native_reader is not None:
            obj = self.native_reader.next_obj()
            if obj is None:
                raise PackReadError("binary pack exhausted before list "
                                    "file (truncated pack?)")
            return obj
        while self.page is None or self.ptop >= self.page.size():
            try:
                page = BinaryPage.load(self.fbin, self.page_ints)
            except Exception as e:   # garbage page header/layout
                raise PackReadError(
                    "corrupt BinaryPage in %s: %s"
                    % (self._epoch_bin_paths[self.bin_idx], e))
            if page is None:
                self.bin_idx += 1
                if self.bin_idx >= len(self._epoch_bin_paths):
                    raise PackReadError("binary pack exhausted before "
                                        "list file (truncated pack?)")
                self.fbin.close()
                self.fbin = open(self._epoch_bin_paths[self.bin_idx], "rb")
                continue
            self.page = page
            self.ptop = 0
        obj = self.page[self.ptop]
        self.ptop += 1
        return obj

    def _next_pair(self):
        """Next (index, label, jpeg-bytes) in on-disk stream order;
        quarantined (previously-corrupt) indices are consumed and
        dropped, and a truncated/corrupt pack ends the epoch early."""
        while True:
            rec = self.lst.next_record()
            if rec is None:
                return None
            index, label, _ = rec
            try:
                buf = self._next_buffer()
            except PackReadError as e:
                if not self.skip_corrupt:
                    raise
                telemetry.count("io.truncated_pack")
                telemetry.event({"ev": "data_corrupt", "source": "imgbin",
                                 "index": int(index),
                                 "reason": "pack: %s" % e})
                sys.stderr.write("WARNING: %s; ending epoch early\n" % e)
                return None
            if int(index) in self._quarantined:
                continue
            return index, label, buf

    def _note_corrupt(self, index, reason) -> None:
        """Skip + count + quarantine a corrupt record by instance index:
        later epochs drop it before decode, so one bad jpeg costs one
        warning, never the run."""
        self._quarantined.add(int(index))
        self._corrupt_seen += 1
        telemetry.count("io.corrupt_records")
        telemetry.event({"ev": "data_corrupt", "source": "imgbin",
                         "index": int(index),
                         "reason": str(reason)[:200]})
        if self.silent == 0 and self._corrupt_seen <= 10:
            sys.stderr.write(
                "WARNING: imgbin record %d undecodable (%s); skipped and "
                "quarantined by index\n" % (int(index), reason))

    def _next_shuffled(self):
        """Instance-level shuffle: draw uniformly from a seeded window of
        upcoming records (each record enters and leaves exactly once, so an
        epoch is a permutation of the corpus)."""
        if not self.shuffle:
            return self._next_pair()
        while len(self._window) < self.shuffle_window:
            p = self._next_pair()
            if p is None:
                break
            self._window.append(p)
        if not self._window:
            return None
        j = int(self._rnd.randint(len(self._window)))
        self._window[j], self._window[-1] = \
            self._window[-1], self._window[j]
        return self._window.pop()

    def _new_pool(self):
        from concurrent.futures import ThreadPoolExecutor
        return ThreadPoolExecutor(max_workers=self.decode_thread,
                                  thread_name_prefix="cxn-decode")

    def _fill_pending(self) -> None:
        if self._pool is None:
            self._pool = self._new_pool()
        while (len(self._pending) < self.buffer_size
               and not self._lst_done):
            p = self._next_shuffled()
            if p is None:
                self._lst_done = True
                break
            index, label, buf = p
            # buf rides the tuple so a pool restart can resubmit it
            self._pending.append(
                (index, label, buf, self._pool.submit(_decode_rgb_chw,
                                                      buf)))

    def _restart_pool(self) -> None:
        """Tear down a pool with a presumed-dead worker and resubmit the
        still-pending decodes to a fresh one. The wedged worker thread
        itself cannot be killed from Python — it is orphaned; nothing
        waits on it anymore."""
        try:
            self._pool.shutdown(wait=False, cancel_futures=True)
        except Exception:
            pass
        self._pool = self._new_pool()
        from collections import deque
        self._pending = deque(
            (i, l, b, self._pool.submit(_decode_rgb_chw, b))
            for (i, l, b, _f) in self._pending)
        telemetry.count("io.decode_worker_restarts")

    def _take_decoded(self, index, fut) -> np.ndarray:
        if self.decode_timeout <= 0:
            return fut.result()
        from concurrent.futures import TimeoutError as _FutTimeout
        try:
            return fut.result(timeout=self.decode_timeout)
        except _FutTimeout:
            # dead/hung decode worker: telemetry first (the stall event
            # the report surfaces), then restart the worker pool
            telemetry.event({"ev": "watchdog_stall", "channel": "io.decode",
                             "stalled_s": self.decode_timeout,
                             "timeout_s": self.decode_timeout,
                             "index": int(index),
                             "action": "restart_pool"})
            telemetry.flush()
            self._restart_pool()
            raise RecordDecodeError(
                "decode of record %d exceeded decode_timeout=%.2fs "
                "(worker presumed dead; pool restarted)"
                % (int(index), self.decode_timeout))

    def next(self) -> bool:
        if self.decode_thread > 1:
            while True:
                self._fill_pending()
                if not self._pending:
                    return False
                index, label, buf, fut = self._pending.popleft()
                try:
                    data = self._take_decoded(index, fut)
                except RecordDecodeError as e:
                    if not self.skip_corrupt:
                        raise
                    self._note_corrupt(index, e)
                    continue
                self.out = DataInst(data, label, index)
                return True
        while True:
            p = self._next_shuffled()
            if p is None:
                return False
            index, label, buf = p
            try:
                data = _decode_rgb_chw(buf)
            except RecordDecodeError as e:
                if not self.skip_corrupt:
                    raise
                self._note_corrupt(index, e)
                continue
            self.out = DataInst(data, label, index)
            return True

    def value(self) -> DataInst:
        return self.out

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
        if self.native_reader is not None:
            closer = getattr(self.native_reader, "close", None)
            if closer is not None:
                closer()
            self.native_reader = None
        if self.fbin is not None:
            self.fbin.close()
            self.fbin = None
        if self.lst is not None:
            self.lst.close()


class ImageIterator(IIterator):
    """img: plain per-file image list iterator (src/io/iter_img-inl.hpp:16)."""

    def __init__(self):
        self.silent = 0
        self.label_width = 1
        self.path_imglst = ""
        self.path_root = ""
        self.shuffle = 0
        self.seed = 0

    def set_param(self, name, val):
        if name == "image_list":
            self.path_imglst = val
        if name == "image_root":
            self.path_root = val
        if name == "silent":
            self.silent = int(val)
        if name == "label_width":
            self.label_width = int(val)
        if name == "shuffle":
            self.shuffle = int(val)
        if name == "seed_data":
            self.seed = int(val)

    def init(self):
        self.records = []
        with open(self.path_imglst) as f:
            for line in f:
                if not line.strip():
                    continue
                toks = line.split()
                index = int(toks[0])
                label = np.asarray(
                    [float(x) for x in toks[1:1 + self.label_width]],
                    np.float32)
                fname = toks[1 + self.label_width]
                self.records.append((index, label, fname))
        if self.shuffle:
            np.random.RandomState(self.seed).shuffle(self.records)
        self.loc = 0

    def before_first(self):
        self.loc = 0

    def next(self) -> bool:
        if self.loc >= len(self.records):
            return False
        index, label, fname = self.records[self.loc]
        self.loc += 1
        path = os.path.join(self.path_root, fname) if self.path_root else fname
        with open(path, "rb") as f:
            data = _decode_rgb_chw(f.read())
        self.out = DataInst(data, label, index)
        return True

    def value(self) -> DataInst:
        return self.out

    def close(self) -> None:
        pass   # records are (index, label, fname) tuples; no handles held


class GeometricAugmenter:
    """cv2 affine pipeline: rotation (+rotate_list), shear, aspect ratio,
    random scale, crop-size range, fill value — one warpAffine
    (reference ImageAugmenter, image_augmenter-inl.hpp:13-140)."""

    def __init__(self):
        self.shape = (0, 0, 0)
        self.rand_crop = 0
        self.max_rotate_angle = 0.0
        self.max_aspect_ratio = 0.0
        self.max_shear_ratio = 0.0
        self.min_crop_size = -1
        self.max_crop_size = -1
        self.rotate = -1.0
        self.rotate_list: List[int] = []
        self.max_random_scale = 1.0
        self.min_random_scale = 1.0
        self.min_img_size = 0.0
        self.max_img_size = 1e10
        self.fill_value = 255
        self.mirror = 0

    def set_param(self, name, val):
        if name == "input_shape":
            self.shape = tuple(int(x) for x in val.split(","))
        if name == "rand_crop":
            self.rand_crop = int(val)
        if name == "max_rotate_angle":
            self.max_rotate_angle = float(val)
        if name == "max_shear_ratio":
            self.max_shear_ratio = float(val)
        if name == "max_aspect_ratio":
            self.max_aspect_ratio = float(val)
        if name == "min_crop_size":
            self.min_crop_size = int(val)
        if name == "max_crop_size":
            self.max_crop_size = int(val)
        if name == "min_random_scale":
            self.min_random_scale = float(val)
        if name == "max_random_scale":
            self.max_random_scale = float(val)
        if name == "min_img_size":
            self.min_img_size = float(val)
        if name == "max_img_size":
            self.max_img_size = float(val)
        if name == "fill_value":
            self.fill_value = int(val)
        if name == "rotate":
            self.rotate = int(val)
        if name == "rotate_list":
            self.rotate_list = [int(x) for x in val.split(",") if x]

    def need_process(self) -> bool:
        return (self.max_rotate_angle > 0 or self.max_shear_ratio > 0
                or self.max_aspect_ratio > 0 or self.rotate > 0
                or len(self.rotate_list) > 0
                or self.max_random_scale != 1.0 or self.min_random_scale != 1.0
                or self.min_crop_size > 0)

    def process(self, data: np.ndarray, rnd: np.random.RandomState) -> np.ndarray:
        """data: (3, h, w) float RGB in [0,255]; returns augmented (3, H, W)."""
        if not self.need_process():
            return data
        import cv2
        # to HWC BGR uint8 for cv2
        src = data.transpose(1, 2, 0)[:, :, ::-1].astype(np.uint8)
        s = rnd.rand() * self.max_shear_ratio * 2 - self.max_shear_ratio
        angle = (rnd.randint(0, max(int(self.max_rotate_angle * 2), 1))
                 - self.max_rotate_angle)
        if self.rotate > 0:
            angle = self.rotate
        if self.rotate_list:
            angle = self.rotate_list[rnd.randint(0, len(self.rotate_list))]
        a = math.cos(angle / 180.0 * math.pi)
        b = math.sin(angle / 180.0 * math.pi)
        scale = rnd.rand() * (self.max_random_scale
                              - self.min_random_scale) + self.min_random_scale
        ratio = rnd.rand() * self.max_aspect_ratio * 2 \
            - self.max_aspect_ratio + 1
        hs = 2 * scale / (1 + ratio)
        ws = ratio * hs
        new_w = max(self.min_img_size, min(self.max_img_size,
                                           scale * src.shape[1]))
        new_h = max(self.min_img_size, min(self.max_img_size,
                                           scale * src.shape[0]))
        M = np.zeros((2, 3), np.float32)
        M[0, 0] = hs * a - s * b * ws
        M[1, 0] = -b * ws
        M[0, 1] = hs * b + s * a * ws
        M[1, 1] = a * ws
        ori_cw = M[0, 0] * src.shape[1] + M[0, 1] * src.shape[0]
        ori_ch = M[1, 0] * src.shape[1] + M[1, 1] * src.shape[0]
        M[0, 2] = (new_w - ori_cw) / 2
        M[1, 2] = (new_h - ori_ch) / 2
        temp = cv2.warpAffine(
            src, M, (int(new_w), int(new_h)), flags=cv2.INTER_CUBIC,
            borderMode=cv2.BORDER_CONSTANT,
            borderValue=(self.fill_value,) * 3)
        # crop to input_shape (reference crops (shape_[1], shape_[2]))
        ch, cw = self.shape[1], self.shape[2]
        y = max(temp.shape[0] - ch, 0)
        x = max(temp.shape[1] - cw, 0)
        if self.rand_crop != 0:
            y = rnd.randint(0, y + 1)
            x = rnd.randint(0, x + 1)
        else:
            y //= 2
            x //= 2
        res = temp[y: y + ch, x: x + cw]
        return np.ascontiguousarray(
            res[:, :, ::-1].transpose(2, 0, 1).astype(np.float32))


class AugmentIterator(IIterator):
    """Per-instance augmentation: crop (random/centered/fixed), mirror,
    scale, mean-image / mean-value subtraction, random contrast and
    illumination (reference AugmentIterator)."""

    kRandMagic = 0
    # mean-image cache header; bump when the stored semantics change
    _MEAN_MAGIC = b"CXNMEAN2"

    def __init__(self, base: IIterator):
        self.base = base
        self.rand_crop = 0
        self.rand_mirror = 0
        self.crop_y_start = -1
        self.crop_x_start = -1
        self.scale = 1.0
        self.silent = 0
        self.name_meanimg = ""
        self.mean_r = 0.0
        self.mean_g = 0.0
        self.mean_b = 0.0
        self.mirror = 0
        self.max_random_illumination = 0.0
        self.max_random_contrast = 0.0
        # output_uint8=1 (TPU-native, beyond the reference): emit raw uint8
        # pixels — crop/mirror only — and defer mean/scale arithmetic to the
        # device (trainer keys input_divideby / input_scale /
        # input_mean_value). Quarters H2D bandwidth vs float32 batches.
        self.output_uint8 = 0
        self.shape = (0, 0, 0)
        self.aug = GeometricAugmenter()
        self.rnd = np.random.RandomState(self.kRandMagic)

    def set_param(self, name, val):
        self.base.set_param(name, val)
        if name == "input_shape":
            self.shape = tuple(int(x) for x in val.split(","))
        if name == "seed_data":
            self.rnd = np.random.RandomState(self.kRandMagic + int(val))
        if name == "rand_crop":
            self.rand_crop = int(val)
        if name == "silent":
            self.silent = int(val)
        if name == "divideby":
            self.scale = 1.0 / float(val)
        if name == "scale":
            self.scale = float(val)
        if name == "image_mean":
            self.name_meanimg = val
        if name == "crop_y_start":
            self.crop_y_start = int(val)
        if name == "crop_x_start":
            self.crop_x_start = int(val)
        if name == "rand_mirror":
            self.rand_mirror = int(val)
        if name == "mirror":
            self.mirror = int(val)
        if name == "max_random_contrast":
            self.max_random_contrast = float(val)
        if name == "max_random_illumination":
            self.max_random_illumination = float(val)
        if name == "mean_value":
            self.mean_b, self.mean_g, self.mean_r = \
                (float(x) for x in val.split(","))
        if name == "output_uint8":
            self.output_uint8 = int(val)
        self.aug.set_param(name, val)

    def init(self):
        self.base.init()
        if self.output_uint8:
            assert not self.name_meanimg, \
                "output_uint8 cannot defer a mean *image*; use " \
                "mean_value/input_mean_value or drop output_uint8"
            assert self.max_random_contrast == 0.0 and \
                self.max_random_illumination == 0.0, \
                "output_uint8 does not support random contrast/illumination"
            assert self.mean_r == self.mean_g == self.mean_b == 0.0, \
                "with output_uint8, move mean_value to the global " \
                "input_mean_value key (subtracted on device)"
            assert self.scale == 1.0, \
                "with output_uint8, move divideby/scale to the global " \
                "input_divideby/input_scale key (applied on device)"
        self.meanfile_ready = False
        self.meanimg = None
        if self.name_meanimg:
            if os.path.exists(self.name_meanimg):
                from ..utils import serializer
                with open(self.name_meanimg, "rb") as f:
                    magic = f.read(len(self._MEAN_MAGIC))
                    if magic == self._MEAN_MAGIC:
                        if self.silent == 0:
                            print("loading mean image from %s"
                                  % self.name_meanimg)
                        self.meanimg = serializer.Reader(f).read_tensor()
                        self.meanfile_ready = True
                    else:
                        # pre-versioned cache: written with scaled-mean
                        # semantics (and possibly the raw-image shape) —
                        # regenerate rather than silently mis-subtract
                        print("mean image %s predates the versioned "
                              "format; regenerating" % self.name_meanimg)
                if not self.meanfile_ready:
                    self._create_mean_img()
            else:
                self._create_mean_img()

    def before_first(self):
        self.base.before_first()

    def _set_data(self, d: DataInst):
        data = d.data
        data = self.aug.process(data, self.rnd)
        c, th, tw = self.shape
        if th == 1:
            img = data.reshape(data.shape[0], 1, -1) if data.ndim == 3 \
                else data
            if self.output_uint8:
                self.out = DataInst(self._to_uint8(img), d.label, d.index)
                return
            out = img * self.scale
            self.out = DataInst(out.astype(np.float32), d.label, d.index)
            return
        assert data.shape[1] >= th and data.shape[2] >= tw, \
            "Data size must be bigger than the input size to net."
        yy = data.shape[1] - th
        xx = data.shape[2] - tw
        if self.rand_crop != 0 and (yy != 0 or xx != 0):
            yy = self.rnd.randint(0, yy + 1)
            xx = self.rnd.randint(0, xx + 1)
        else:
            yy //= 2
            xx //= 2
        if data.shape[1] != th and self.crop_y_start != -1:
            yy = self.crop_y_start
        if data.shape[2] != tw and self.crop_x_start != -1:
            xx = self.crop_x_start
        contrast = (self.rnd.rand() * self.max_random_contrast * 2
                    - self.max_random_contrast + 1)
        illumination = (self.rnd.rand() * self.max_random_illumination * 2
                        - self.max_random_illumination)
        do_mirror = (self.rand_mirror != 0 and self.rnd.rand() < 0.5) \
            or self.mirror == 1
        if self.mean_r > 0.0 or self.mean_g > 0.0 or self.mean_b > 0.0:
            base = data.copy()
            base[0] -= self.mean_b
            base[1] -= self.mean_g
            base[2] -= self.mean_r
            img = base[:, yy: yy + th, xx: xx + tw] * contrast + illumination
        elif not self.meanfile_ready or not self.name_meanimg:
            img = data[:, yy: yy + th, xx: xx + tw].astype(np.float32)
            contrast, illumination = 1.0, 0.0  # reference applies none here
        else:
            if data.shape == self.meanimg.shape:
                img = ((data - self.meanimg)[:, yy: yy + th, xx: xx + tw]
                       * contrast + illumination)
            else:
                img = ((data[:, yy: yy + th, xx: xx + tw] - self.meanimg)
                       * contrast + illumination)
        if do_mirror:
            img = img[:, :, ::-1]
        if self.output_uint8:
            self.out = DataInst(self._to_uint8(img), d.label, d.index)
            return
        self.out = DataInst(
            np.ascontiguousarray(img * self.scale, dtype=np.float32),
            d.label, d.index)

    def next(self) -> bool:
        if not self.base.next():
            return False
        self._set_data(self.base.value())
        return True

    def value(self) -> DataInst:
        return self.out

    def close(self) -> None:
        self.base.close()

    @staticmethod
    def _to_uint8(img: np.ndarray) -> np.ndarray:
        # decode yields exact integer-valued floats; warpAffine may not —
        # round, don't truncate
        return np.ascontiguousarray(
            np.clip(np.rint(img), 0, 255).astype(np.uint8))

    def _create_mean_img(self):
        """Compute and cache the dataset mean image
        (reference CreateMeanImg, iter_augment_proc-inl.hpp:171-198).

        The mean lives in the NET-INPUT shape: it averages the augmented,
        cropped outputs of one pass (meanfile_ready is False here, so
        _set_data takes the no-subtract branch) — the reference sizes
        meanimg_ to shape_ and accumulates img_, which is what makes
        subtraction valid when geometric augmentation changes the raw
        image size. One deliberate divergence: the reference accumulates
        img_ AFTER `* scale_` yet subtracts it from raw pixels at use
        (iter_augment_proc-inl.hpp:142,148 — with divideby set, mean
        centering is silently ~nullified); we accumulate unscaled values
        so (x - mean) * scale means what it says. The cached file format
        is ours (utils/serializer), not mshadow's, so no interchange is
        lost."""
        if self.silent == 0:
            print("cannot find %s: create mean image, this will take "
                  "some time..." % self.name_meanimg)
        self.base.before_first()
        mean = None
        cnt = 0
        saved_scale, self.scale = self.scale, 1.0
        try:
            while self.base.next():
                self._set_data(self.base.value())
                d = self.out.data
                if mean is None:
                    mean = d.astype(np.float64).copy()
                else:
                    mean += d
                cnt += 1
        finally:
            self.scale = saved_scale
        assert cnt > 0, "input iterator failed."
        self.meanimg = (mean / cnt).astype(np.float32)
        from ..utils import serializer
        parent = os.path.dirname(self.name_meanimg)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(self.name_meanimg, "wb") as f:
            f.write(self._MEAN_MAGIC)
            serializer.Writer(f).write_tensor(self.meanimg)
        if self.silent == 0:
            print("save mean image to %s.." % self.name_meanimg)
        self.meanfile_ready = True
        self.base.before_first()


def create_image_base(kind: str) -> IIterator:
    """imgbin chains come pre-wrapped Batch(Augment(PageReader))
    (reference data.cpp:35-50)."""
    if kind in ("imgbin", "imgbinx"):
        page_it = ImagePageIterator()
        if kind == "imgbinx":
            # imgbinx is the pipelined variant: decode pool on by default
            page_it.decode_thread = 4
        return BatchAdaptIterator(AugmentIterator(page_it))
    if kind == "img":
        return BatchAdaptIterator(AugmentIterator(ImageIterator()))
    raise ValueError("unknown image iterator %s" % kind)
