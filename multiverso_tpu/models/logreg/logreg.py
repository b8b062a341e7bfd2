"""LogReg driver: config-file-driven train/test loop.

Behavioral equivalent of reference
Applications/LogisticRegression/src/logreg.cpp: construct from a config
file (main.cpp:8-12), ``Train`` streams windows from the async reader
through the model (logreg.cpp:40-87, with per-``show_time_per_sample``
throughput logging), ``Test`` scores the test file and writes predictions
(logreg.cpp:121-172), ``SaveModel`` persists the weights.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Union

import numpy as np

from multiverso_tpu.models.logreg.configure import Configure
from multiverso_tpu.models.logreg.data import (WindowReader, batch_samples,
                                               iter_samples)
from multiverso_tpu.models.logreg.model import Model
from multiverso_tpu.utils.log import Log
from multiverso_tpu.utils.timer import Timer


class LogReg:
    def __init__(self, config: Union[str, Configure]):
        if isinstance(config, str):
            config = Configure.from_file(config)
        config.finalize()
        self.config = config
        from multiverso_tpu.utils.world import WorldOwner
        self._world = WorldOwner()
        if config.use_ps:
            self._world.init_if_needed()
        # exception-safe: model/table construction after MV_Init must not
        # strand a started Zoo (same obligation as the WE driver)
        with self._world.guard("logreg.init"):
            self.model = Model.Get(config)
            # per-worker output files in PS mode so concurrent workers don't
            # clobber each other (reference ps_model.cpp:43-46 appends
            # -<worker_id>); kept as instance paths — the caller's Configure
            # is never mutated
            self.output_model_file = config.output_model_file
            self.output_file = config.output_file
            if config.use_ps:
                import multiverso_tpu as mv
                wid = mv.MV_WorkerId()
                if self.output_model_file:
                    self.output_model_file += f"-{wid}"
                if self.output_file:
                    self.output_file += f"-{wid}"
            if config.init_model_file and not config.use_ps:
                self.model.Load(config.init_model_file)

    def Train(self, train_file: Optional[str] = None) -> float:
        """One full training run (config.train_epoch epochs); returns the
        final epoch's average train loss per sample."""
        with self._world.guard("logreg.Train"):
            return self._train(train_file)

    def _train(self, train_file: Optional[str] = None) -> float:
        cfg = self.config
        files = train_file or cfg.train_file
        avg_loss = 0.0
        log_threads: list = []
        cache = None
        if cfg.cache_data:
            from multiverso_tpu.models.logreg.data import WindowCache
            cache = WindowCache(cfg.cache_data_mb)
        from multiverso_tpu.parallel import multihost
        collective = (cfg.device_plane and cfg.use_ps
                      and multihost.process_count() > 1
                      and getattr(self.model, "_device_trainer",
                                  None) is not None)

        filler_window = None

        def pop_window(reader):
            """reader.next_window, multi-process device-plane safe: the
            window programs are COLLECTIVE, so finished ranks keep
            joining with empty filler windows (inert: weight-0 batches,
            lr 0; ONE filler object is reused so its device-staged zero
            tensors upload once) until every rank's shard is done. One
            allgather per window also agrees the sparse statics (shared
            K, key count) and the GLOBAL sample count — the window loss
            the collective program returns is global, so the per-sample
            metrics must divide by global samples."""
            nonlocal filler_window
            w = reader.next_window()
            if not collective:
                return w
            local_n = (sum(b.count for b in w.batches)
                       if w is not None else 0)
            if cfg.sparse:
                kmax = (max((b.keys.shape[1] for b in w.batches),
                            default=1) if w is not None else 1)
                nk = len(w.keys) if w is not None else 0
            else:
                kmax = nk = 0
            parts = multihost.host_allgather_objects_capped(
                (w is None, kmax, nk, local_n), "lr_pop")
            if all(p[0] for p in parts):
                return None
            if w is None:
                if filler_window is None:
                    from multiverso_tpu.models.logreg.data import Window
                    import numpy as np
                    filler_window = Window(batches=[],
                                           keys=np.empty(0, np.int64))
                w = filler_window
            w._dp_agreed = ((max(p[1] for p in parts),
                             max(max(p[2] for p in parts), 1))
                            if cfg.sparse else ())
            w._global_count = sum(p[3] for p in parts)
            return w

        for epoch in range(cfg.train_epoch):
            reader = (cache.reader(files, cfg, cfg.sync_frequency)
                      if cache is not None
                      else WindowReader(files, cfg, cfg.sync_frequency))
            timer = Timer()
            samples = 0
            loss_sum = 0.0
            next_report = cfg.show_time_per_sample
            while True:
                window = pop_window(reader)
                if window is None:
                    break
                loss_sum += self.model.train_window(window)
                # collective mode: the returned loss is GLOBAL (all
                # processes' batches), so count global samples too
                samples += (window._global_count if collective
                            else sum(b.count for b in window.batches))
                if samples >= next_report:
                    Log.Info("[logreg] epoch %d: %d samples, "
                             "%.1f samples/s, avg loss %.5f", epoch, samples,
                             samples / max(timer.elapse(), 1e-9),
                             loss_sum / max(samples, 1))
                    next_report += cfg.show_time_per_sample
                    self.model.DisplayTime()
            avg_loss = loss_sum / max(samples, 1)
            if cfg.device_plane:
                # device-plane losses are DEVICE scalars: formatting one
                # forces a device->host fetch that would barrier the
                # pipeline once per epoch. Emit the epoch line from a
                # harvest thread instead — the fetch waits on the device
                # there while the training loop keeps dispatching.
                t = threading.Thread(
                    target=Log.Info,
                    args=("[logreg] epoch %d done: %d samples, avg loss "
                          "%.5f, %.2fs", epoch, samples, avg_loss,
                          timer.elapse()),
                    daemon=True)
                t.start()
                log_threads.append(t)
            else:
                Log.Info("[logreg] epoch %d done: %d samples, avg loss "
                         "%.5f, %.2fs", epoch, samples, avg_loss,
                         timer.elapse())
        for t in log_threads:
            t.join()  # unbounded-ok: epoch workers finished their sample loop
        if cfg.use_ps:
            import multiverso_tpu as mv
            mv.MV_Barrier()
        if self.output_model_file:
            self.SaveModel()
        # API boundary: device_plane windows return 0-d jax arrays, so
        # avg_loss may be a device scalar here — convert (one already-
        # landed copy; the harvest threads overlapped the fetch)
        return float(avg_loss)

    def Test(self, test_file: Optional[str] = None) -> float:
        """Score the test set; writes per-sample predictions to
        config.output_file; returns accuracy (reference logreg.cpp:121-172
        counts correct predictions)."""
        cfg = self.config
        files = test_file or cfg.test_file
        if not files:
            Log.Info("[logreg] no test file; skip test")
            return 0.0
        with self._world.guard("logreg.Test"):
            return self._test(files)

    def _test(self, files) -> float:
        cfg = self.config
        correct = 0
        total = 0
        out_lines = []
        pending = []
        W = self.model.weights()  # one pull for the whole test pass
        for sample in iter_samples(files, cfg):
            pending.append(sample)
            if len(pending) == cfg.minibatch_size:
                correct_, total_ = self._score(pending, out_lines, W)
                correct += correct_
                total += total_
                pending = []
        if pending:
            correct_, total_ = self._score(pending, out_lines, W)
            correct += correct_
            total += total_
        if self.output_file:
            with open(self.output_file, "w") as f:
                f.write("\n".join(out_lines) + "\n")
        acc = correct / max(total, 1)
        Log.Info("[logreg] test: %d/%d correct (%.4f)", correct, total, acc)
        return acc

    def _score(self, pending, out_lines, W=None):
        cfg = self.config
        batch = batch_samples(pending, cfg, cfg.minibatch_size)
        preds = self.model.predict_batch(batch, W)
        labels = batch.labels[: batch.count]
        if cfg.output_size > 1:
            hard = np.argmax(preds, axis=1)
        else:
            hard = (preds[:, 0] >= 0.5).astype(np.int32)
        for p, h in zip(preds, hard):
            out_lines.append(" ".join(f"{x:.6f}" for x in np.atleast_1d(p))
                             + f" -> {h}")
        return int(np.sum(hard == labels)), int(batch.count)

    def SaveModel(self, path: Optional[str] = None) -> None:
        self.model.Store(path or self.output_model_file)

    def close(self) -> None:
        self._world.close()
