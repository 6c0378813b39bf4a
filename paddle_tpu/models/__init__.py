"""paddle_tpu.models — LLM model families (reference ecosystem: PaddleNLP)."""
from .bert import (BertConfig, BertForMaskedLM,  # noqa: F401
                   BertForSequenceClassification, BertModel)
from .gpt import (GPTConfig, GPTForCausalLM, GPTForCausalLMPipe,  # noqa: F401
                  GPTModel)
from .llama import (LlamaConfig, LlamaForCausalLM,  # noqa: F401
                    LlamaForCausalLMPipe, LlamaModel,
                    LlamaPretrainingCriterion, count_params,
                    flops_per_token)
from .latent_moe import (LatentMoEConfig,  # noqa: F401
                         LatentMoEForCausalLM)
from .sambay import SambaYConfig, SambaYForCausalLM  # noqa: F401
from .kda_moe import KdaMoEConfig, KdaMoEForCausalLM  # noqa: F401
from .t5 import (T5Config, T5ForConditionalGeneration,  # noqa: F401
                 T5Model)
from .whisper import (WhisperConfig, WhisperModel,  # noqa: F401
                      WhisperForConditionalGeneration)
from .clip import (CLIPConfig, CLIPModel, CLIPTextConfig,  # noqa: F401
                   CLIPVisionConfig, clip_loss, clip_global_loss)
from .wav2vec2 import (Wav2Vec2Config, Wav2Vec2Model,  # noqa: F401
                       Wav2Vec2ForCTC)
from .ddpm import (UNet2DConfig, UNet2DModel, DDPMScheduler,  # noqa: F401
                   DDIMScheduler, ddpm_train_loss)
from .deepfm import DeepFM, DeepFMConfig  # noqa: F401
from .dcgan import (DCGANConfig, Generator as DCGANGenerator,  # noqa: F401
                    Discriminator as DCGANDiscriminator,
                    gan_bce_losses)
from .albert import AlbertConfig, AlbertModel  # noqa: F401
from .roberta import RobertaConfig, RobertaModel  # noqa: F401
