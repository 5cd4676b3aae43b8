"""Entry ``trainer_fit``: one ``DeepTextClassifier.fit``, the window inside it.

The estimator's normal path: ``hash_tokenize`` -> ``TransformerEncoder`` ->
``FlaxTrainer._fit_spmd``, one device, no mesh. The configuration gives the
encoder's sizes under the source's own keys (``hidden_size`` ...) and the
rest of the estimator's parameters under ``estimator``; the traffic file
gives the texts (``benchmark/texts.py``), ``judged_steps`` and
``max_epochs`` (the learning rate is constant, so the count of epochs
changes no arithmetic and no program).

The fit runs on a thread of the entry's own. Its step hook (``stepFn``)
holds it at the end of every epoch until the harness asks for the next one,
so one unit of work is one epoch, from the last step of the epoch before to
its own last step: the steps, the epoch's bookkeeping, the next
permutation and the prefetch's refill. Epoch 0 is set-up: it compiles
``train_step``, and the hook copies to the host what the reference judges
(the loss of steps 0..2, the first moment after step 0, the parameters
after step 2). Nothing is copied after that. The work a unit reports is the
samples of the steps the hook saw, checked against the program's own epoch
record. The fit is ended from the hook once the window has closed, at the
first step after it, so that the trainer has logged the last epoch's record.
"""

from __future__ import annotations

import json
import logging
import queue
import sys
import threading
import time

import numpy as np

from benchmark import texts

# the estimator's parameter for each of the source's keys
_SIZES = {"hidden_size": "hiddenSize", "num_hidden_layers": "numLayers",
          "num_attention_heads": "numHeads",
          "max_position_embeddings": "maxTokenLen", "vocab_size": "vocabSize"}


class _WindowClosed(Exception):
    """Raised from the step hook to end the fit."""


class _Epochs(logging.Handler):
    """Catches the estimator's ``epoch`` records: the trainer's own history
    entry of every epoch (steps, seconds, and the sums of its step spans)."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record):
        try:
            payload = json.loads(record.getMessage())
        except ValueError:
            return
        if payload.get("method") == "epoch":
            self.records.append(payload)


def _flat(tree) -> dict:
    """{'a/b/c': host array} of a parameter tree, copied off the device."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = np.asarray(
            leaf)
    return out


class Entry:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        self.config, self.traffic = config, traffic
        self.seed, self.chips = seed, chips
        self.batch = int(config["estimator"]["batchSize"])
        self.judged_steps = int(traffic["judged_steps"])
        self.classes = int(traffic["texts"]["classes"])
        self.epochs = []         # the program's record of every epoch
        self.judged = {"losses": []}
        self._to_main = queue.Queue()
        self._go = threading.Semaphore(0)
        self._stop = False
        self._steps_seen = 0
        self._opt_count = None
        self.unit_seconds = []

    # -- set-up -------------------------------------------------------------
    def setup(self):
        from synapseml_tpu.core import Table
        from synapseml_tpu.dl.text import DeepTextClassifier

        self.texts, self.labels = texts.make(self.traffic["texts"], self.seed)
        self.steps_per_epoch = len(self.texts) // self.batch
        if self.steps_per_epoch <= self.judged_steps:
            raise ValueError("an epoch must hold the judged steps")
        self._handler = _Epochs()
        log = logging.getLogger("synapseml_tpu")
        log.addHandler(self._handler)
        log.setLevel(logging.DEBUG)
        log.propagate = False
        est = DeepTextClassifier(
            **{ours: self.config[theirs] for theirs, ours in _SIZES.items()},
            **self.config["estimator"], seed=self.seed,
            maxEpochs=int(self.traffic["max_epochs"]), stepFn=self._hook)
        table = Table({"text": self.texts, "label": self.labels})

        def run():
            try:
                est.fit(table)
                self._to_main.put(("ended", None))
            except _WindowClosed:
                self._to_main.put(("ended", None))
            except BaseException as e:     # handed to the harness's thread
                self._to_main.put(("error", e))

        self._thread = threading.Thread(target=run, name="bench-fit",
                                        daemon=True)
        self._thread.start()
        self._wait_epoch()       # epoch 0: compilation and the judged steps

    # -- the step hook, on the fit's thread ---------------------------------
    def _hook(self, step, loss, params, batch_stats, opt_state):
        if self._stop:
            # one step past the window: the last epoch's record is logged
            raise _WindowClosed
        if step < self.judged_steps:
            self.judged["losses"].append(float(loss))
            if step == 0:
                self.judged["mu"] = _flat(opt_state[0].mu)
            if step == self.judged_steps - 1:
                self.judged["params"] = _flat(params)
        self._steps_seen += 1
        if (step + 1) % self.steps_per_epoch == 0:
            self._opt_count = int(opt_state[0].count)
            self._to_main.put(("epoch", self.steps_per_epoch))
            self._go.acquire()

    def _wait_epoch(self) -> int:
        what, value = self._to_main.get()
        if what == "error":
            raise value
        if what == "ended":
            raise RuntimeError("the fit ended before the window closed: "
                               "raise max_epochs in the traffic file")
        return value

    # -- one epoch ------------------------------------------------------------
    def unit(self) -> int:
        t0 = time.perf_counter()
        self._go.release()
        steps = self._wait_epoch()
        self.unit_seconds.append(time.perf_counter() - t0)
        return steps * self.batch

    # -- what the reference judges -------------------------------------------
    def check_inputs(self) -> dict:
        """Ends the fit (it is held at the end of the window's last epoch)
        and hands over what epoch 0 kept and what the counts say."""
        self._stop = True
        self._go.release()
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            raise RuntimeError("the fit did not end")
        self.epochs = list(self._handler.records)
        print("units (s):", [round(u, 4) for u in self.unit_seconds],
              "the trainer's epochs (s, median step ms):",
              [(round(e["seconds"], 4), round(e["step_ms_p50"], 3))
               for e in self.epochs], file=sys.stderr)
        return {"texts": self.texts, "labels": self.labels, "seed": self.seed,
                "batch": self.batch, "steps": self.judged_steps,
                "judged": dict(
                    self.judged, hook_steps=self._steps_seen,
                    opt_count=self._opt_count,
                    program_steps=sum(int(e["steps"]) for e in self.epochs))}

    def traced_epoch(self):
        """The program's record of the window's first epoch, or None."""
        return self.epochs[1] if len(self.epochs) > 1 else None

    def release(self):
        """Drop what the program left on the device before the reference
        runs."""
        import jax

        logging.getLogger("synapseml_tpu").removeHandler(self._handler)
        jax.clear_caches()
