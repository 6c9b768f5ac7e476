"""RadioML-style I/Q frames, generated from a seed.

The benchmark's own copy of the synthetic generator, so that a change to
the program cannot change the traffic: complex baseband frames,
root-raised-cosine pulse shaping for the linear schemes,
Gaussian/continuous-phase FSK, an AR(2) audio-like source for the analog
schemes, and a channel with a random carrier frequency and phase offset,
phase noise and AWGN at the frame's SNR.  Every frame is deterministic in
(seed, index).  Two class sets (a traffic's ``frames.classes``):

* ``radioml2016`` (the default): RadioML 2016.10a's 11 modulations.
* ``radioml2018``: RadioML 2018.01A's 24.  Built from the same
  primitives, with these assumptions (the dataset's generator is not
  published):

  - OOK, 4ASK, 8ASK: unipolar amplitude levels 0 ... M-1, RRC-shaped;
  - 16APSK 4+12 rings, radius ratio 2.85; 32APSK 4+12+16, 2.84 and
    5.27 (DVB-S2, rate 3/4); 64APSK 4+12+20+28, 2.4, 4.3 and 7.0;
    128APSK 16+16+16+16+16+48, 1.715, 2.118, 2.312, 2.851 and 3.589
    (DVB-S2X); each ring's points equally spaced, offset half a step;
  - 32QAM and 128QAM: cross constellations (a 6x6 and a 12x12 square
    without its corners);
  - OQPSK: QPSK with the quadrature rail half a symbol (SPS/2 samples)
    late, RRC-shaped;
  - GMSK: BT 0.3, modulation index 1/2;
  - AM-DSB-WC, AM-SSB-WC: carrier plus the 0.8-scaled audio (upper
    sideband for SSB); AM-DSB-SC, AM-SSB-SC: the same without carrier;
    FM: as 2016's WBFM.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

MODULATIONS = (
    "BPSK", "QPSK", "8PSK", "PAM4", "QAM16", "QAM64", "GFSK", "CPFSK",
    "WBFM", "AM-DSB", "AM-SSB",
)
MODULATIONS_2018 = (
    "OOK", "4ASK", "8ASK", "BPSK", "QPSK", "8PSK", "16PSK", "32PSK",
    "16APSK", "32APSK", "64APSK", "128APSK",
    "16QAM", "32QAM", "64QAM", "128QAM", "256QAM",
    "AM-SSB-WC", "AM-SSB-SC", "AM-DSB-WC", "AM-DSB-SC", "FM", "GMSK", "OQPSK",
)
CLASS_SETS = {"radioml2016": MODULATIONS, "radioml2018": MODULATIONS_2018}
SPS = 8          # samples per symbol of the linear digital schemes
GAUSS_BT = 0.35  # GFSK's Gaussian filter bandwidth-time product
GMSK_BT = 0.3
AM_INDEX = 0.8


def _rrc_taps(beta: float = 0.35, span: int = 8, sps: int = SPS) -> np.ndarray:
    """Root-raised-cosine taps with the two removable singularities filled."""
    n = span * sps
    t = np.arange(-n // 2, n // 2 + 1) / sps
    near_zero = np.abs(t) < 1e-9
    singular = np.abs(np.abs(4 * beta * t) - 1.0) < 1e-9
    with np.errstate(divide="ignore", invalid="ignore"):
        num = (np.sin(np.pi * t * (1 - beta))
               + 4 * beta * t * np.cos(np.pi * t * (1 + beta)))
        taps = num / (np.pi * t * (1 - (4 * beta * t) ** 2))
    taps = np.where(
        singular,
        (beta / np.sqrt(2)) * ((1 + 2 / np.pi) * np.sin(np.pi / (4 * beta))
                               + (1 - 2 / np.pi) * np.cos(np.pi / (4 * beta))),
        taps)
    taps = np.where(near_zero, 1.0 - beta + 4 * beta / np.pi, taps)
    return taps / np.sqrt(np.sum(taps ** 2))


def _gaussian_taps(bt: float = GAUSS_BT, span: int = 4,
                   sps: int = SPS) -> np.ndarray:
    t = np.arange(-span * sps // 2, span * sps // 2 + 1) / sps
    sigma = np.sqrt(np.log(2)) / (2 * np.pi * bt)
    taps = np.exp(-(t ** 2) / (2 * sigma ** 2))
    return taps / taps.sum()


_RRC = _rrc_taps()
_GAUSS = _gaussian_taps()
_GMSK = _gaussian_taps(GMSK_BT)


def _psk(m: int) -> np.ndarray:
    k = np.arange(m)
    return np.exp(1j * (2 * np.pi * k / m + np.pi / m))


def _qam(m: int) -> np.ndarray:
    side = int(np.sqrt(m))
    re, im = np.meshgrid(np.arange(side), np.arange(side))
    pts = ((2 * re - side + 1) + 1j * (2 * im - side + 1)).ravel()
    return pts / np.sqrt((np.abs(pts) ** 2).mean())


def _pam(m: int) -> np.ndarray:
    pts = 2 * np.arange(m) - m + 1
    return (pts / np.sqrt((pts ** 2).mean())).astype(complex)


def _ask(m: int) -> np.ndarray:
    pts = np.arange(m, dtype=float)
    return (pts / np.sqrt((pts ** 2).mean())).astype(complex)


def _cross_qam(side: int, corner: int) -> np.ndarray:
    """A ``side``-square grid less a ``corner``-square at each corner."""
    re, im = np.meshgrid(np.arange(side), np.arange(side))
    pts = ((2 * re - side + 1) + 1j * (2 * im - side + 1)).ravel()
    edge = side - 1 - 2 * corner
    pts = pts[(np.abs(pts.real) <= edge) | (np.abs(pts.imag) <= edge)]
    return pts / np.sqrt((np.abs(pts) ** 2).mean())


def _apsk(rings: Sequence[int], radii: Sequence[float]) -> np.ndarray:
    pts = np.concatenate([r * _psk(n) for n, r in zip(rings, radii)])
    return pts / np.sqrt((np.abs(pts) ** 2).mean())


_CONSTELLATIONS = {"BPSK": _psk(2), "QPSK": _psk(4), "8PSK": _psk(8),
                   "PAM4": _pam(4), "QAM16": _qam(16), "QAM64": _qam(64),
                   "OOK": _ask(2), "4ASK": _ask(4), "8ASK": _ask(8),
                   "16PSK": _psk(16), "32PSK": _psk(32),
                   "16APSK": _apsk((4, 12), (1.0, 2.85)),
                   "32APSK": _apsk((4, 12, 16), (1.0, 2.84, 5.27)),
                   "64APSK": _apsk((4, 12, 20, 28), (1.0, 2.4, 4.3, 7.0)),
                   "128APSK": _apsk((16, 16, 16, 16, 16, 48),
                                    (1.0, 1.715, 2.118, 2.312, 2.851, 3.589)),
                   "16QAM": _qam(16), "32QAM": _cross_qam(6, 1),
                   "64QAM": _qam(64), "128QAM": _cross_qam(12, 2),
                   "256QAM": _qam(256)}


def _audio_like(rng: np.random.Generator, n: int) -> np.ndarray:
    """Lowpass AR(2) source, normalised to unit peak."""
    w = rng.normal(size=n + 64)
    x = np.zeros_like(w)
    for i in range(2, len(w)):
        x[i] = w[i] + 1.6 * x[i - 1] - 0.72 * x[i - 2]
    x = x[64:]
    return x / (np.max(np.abs(x)) + 1e-9)


_ANALOG = ("WBFM", "FM", "AM-DSB", "AM-DSB-WC", "AM-DSB-SC", "AM-SSB",
           "AM-SSB-WC", "AM-SSB-SC")


def _oqpsk(rng: np.random.Generator, n: int) -> np.ndarray:
    n_sym = n // SPS + len(_RRC) // SPS + 4
    up = np.zeros(n_sym * SPS, dtype=complex)
    up[::SPS] = rng.integers(0, 2, n_sym) * 2.0 - 1.0
    up[SPS // 2::SPS] += 1j * (rng.integers(0, 2, n_sym) * 2.0 - 1.0)
    start = len(_RRC) // 2
    return np.convolve(up, _RRC, mode="same")[start:start + n] / np.sqrt(2)


def _modulate(rng: np.random.Generator, scheme: str, n: int) -> np.ndarray:
    if scheme in _CONSTELLATIONS:
        const = _CONSTELLATIONS[scheme]
        n_sym = n // SPS + len(_RRC) // SPS + 4
        up = np.zeros(n_sym * SPS, dtype=complex)
        up[::SPS] = const[rng.integers(0, len(const), n_sym)]
        start = len(_RRC) // 2
        return np.convolve(up, _RRC, mode="same")[start:start + n]
    if scheme == "OQPSK":
        return _oqpsk(rng, n)
    if scheme in ("GFSK", "CPFSK", "GMSK"):
        bits = rng.integers(0, 2, n // SPS + 8) * 2.0 - 1.0
        freq = np.repeat(bits, SPS)
        if scheme != "CPFSK":
            freq = np.convolve(freq, _GMSK if scheme == "GMSK" else _GAUSS,
                               mode="same")
        return np.exp(1j * np.cumsum(freq) * np.pi * 0.5 / SPS)[:n]
    if scheme not in _ANALOG:
        raise ValueError(f"frames: unknown modulation {scheme!r}")
    x = _audio_like(rng, n)
    if scheme in ("WBFM", "FM"):
        return np.exp(1j * 2 * np.pi * 0.4 * np.cumsum(x))
    if scheme in ("AM-DSB", "AM-DSB-WC"):
        return (1.0 + AM_INDEX * x).astype(complex)
    if scheme == "AM-DSB-SC":
        return x.astype(complex)
    h = np.zeros(n)                       # AM-SSB: upper sideband (Hilbert)
    h[0] = 1
    h[n // 2] = 1
    h[1:n // 2] = 2
    ssb = np.fft.ifft(np.fft.fft(x) * h)
    return 1.0 + AM_INDEX * ssb if scheme == "AM-SSB-WC" else ssb


def _channel(rng: np.random.Generator, sig: np.ndarray,
             snr_db: float) -> np.ndarray:
    """Carrier frequency/phase offset, phase noise, then AWGN at ``snr_db``."""
    n = len(sig)
    cfo = rng.uniform(-0.01, 0.01)
    phi0 = rng.uniform(0, 2 * np.pi)
    sig = sig * np.exp(1j * (2 * np.pi * cfo * np.arange(n) + phi0))
    sig = sig * np.exp(1j * np.cumsum(rng.normal(scale=2e-3, size=n)))
    sig = sig / np.sqrt(np.mean(np.abs(sig) ** 2) + 1e-12)
    p_noise = 10 ** (-snr_db / 10)
    return sig + (rng.normal(size=n) + 1j * rng.normal(size=n)) \
        * np.sqrt(p_noise / 2)


def frame(seed: int, scheme: str, snr_db: float,
          frame_len: int = 128) -> np.ndarray:
    """One (2, frame_len) float32 frame of roughly unit energy."""
    rng = np.random.default_rng(seed)
    sig = _channel(rng, _modulate(rng, scheme, frame_len), snr_db)
    out = np.stack([sig.real, sig.imag]).astype(np.float32)
    out = out / (np.sqrt(np.mean(out ** 2)) * np.sqrt(2) + 1e-9)
    return out.astype(np.float32)


def frame_pool(seed: int, n: int, snr_grid: Sequence[float],
               classes: Optional[Sequence[int]] = None,
               frame_len: int = 128, class_set: str = "radioml2016"
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``n`` frames with classes and SNRs drawn uniformly from the lists.

    ``classes`` are indices into the class set (all of it by default).
    Returns (iq (n, 2, frame_len) float32, labels (n,), snrs (n,)).
    """
    schemes = CLASS_SETS[class_set]
    if classes is None:
        classes = range(len(schemes))
    rng = np.random.default_rng([seed, 0x5EED])
    labels = np.asarray(classes)[rng.integers(0, len(classes), n)]
    snrs = np.asarray(snr_grid, np.float64)[rng.integers(0, len(snr_grid), n)]
    children = np.random.SeedSequence([seed, 0xF4A3]).spawn(n)
    iq = np.stack([frame(children[i], schemes[labels[i]], float(snrs[i]),
                         frame_len) for i in range(n)])
    return iq, labels.astype(np.int32), snrs.astype(np.float32)
